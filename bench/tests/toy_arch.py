"""A toy architecture the tests add as ``archs/toy.py`` to a copy of the
benchmark: ``dense_gqa`` with its SwiGLU MLP replaced by a routed mixture
of SwiGLU experts (softmax router, the top ``top_k`` experts of each token,
their weights renormalised over the k), on the registry's MoE entry."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from archs import dense_gqa
from harness.reference import HI, mm, rmsnorm, round_fp8, stack

CONFIG_KEYS = {**dense_gqa.CONFIG_KEYS,
               "intermediate_size": "moe.d_ff_expert",
               "num_local_experts": "moe.num_experts",
               "num_experts_per_tok": "moe.top_k"}


@dataclasses.dataclass(frozen=True)
class Dims(dense_gqa.Dims):
    experts: int
    top_k: int

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        base = dataclasses.asdict(dense_gqa.Dims.from_config(c))
        return cls(**base, experts=c["num_local_experts"],
                   top_k=c["num_experts_per_tok"])

    @classmethod
    def from_model(cls, cfg) -> "Dims":
        base = dataclasses.asdict(dense_gqa.Dims.from_model(cfg))
        base["d_ff"] = cfg.moe.d_ff_expert
        return cls(**base, experts=cfg.moe.num_experts, top_k=cfg.moe.top_k)


def check(cfg, config: dict) -> None:
    if not cfg.is_moe or cfg.moe.num_shared_experts:
        raise ValueError(f"{cfg.arch_id}: not a routed MoE")
    dense_gqa.check(cfg.replace(moe=None), config)


def shapes(d: Dims) -> dict:
    tree = dense_gqa.shapes(d)
    L, D, E, F = d.layers, d.d_model, d.experts, d.d_ff
    tree["layers"]["mlp"] = {
        "router": ((L, D, E), D), "w_gate": ((L, E, D, F), D),
        "w_up": ((L, E, D, F), D), "w_down": ((L, E, F, D), F)}
    return tree


def _experts(d, m, ln, x):
    y = rmsnorm(ln, x, d.rms_eps)
    probs = jax.nn.softmax(mm(y, m["router"]), -1)
    top, idx = jax.lax.top_k(probs, d.top_k)
    rows = jnp.arange(x.shape[0])[:, None]
    gate = jnp.zeros_like(probs).at[rows, idx].set(
        top / top.sum(-1, keepdims=True))
    g = jnp.einsum("sd,edf->esf", y, m["w_gate"], precision=HI)
    u = jnp.einsum("sd,edf->esf", y, m["w_up"], precision=HI)
    out = jnp.einsum("esf,efd->esd", jax.nn.silu(g) * u, m["w_down"],
                     precision=HI)
    return x + jnp.einsum("se,esd->sd", gate, out, precision=HI)


def _layer(d, fp8, x, lp):
    lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
    a, m = dict(lp["attn"]), dict(lp["mlp"])
    if fp8:
        a = {k: round_fp8(v) for k, v in a.items()}
        m = {k: round_fp8(v) for k, v in m.items()}
    x = dense_gqa.attention(d, a, lp["ln1"], x)
    return _experts(d, m, lp["ln2"], x), None


@functools.partial(jax.jit, static_argnames=("d", "fp8"))
def logits(params, tokens, d, fp8: bool = False):
    return stack(functools.partial(_layer, d, fp8), params, tokens,
                 d.rms_eps, fp8)
