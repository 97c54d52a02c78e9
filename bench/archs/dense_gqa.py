"""Dense decoder: RoPE, causal grouped-query attention, SwiGLU MLP, untied head.

internlm2-1.8b and phi3-mini-3.8b.  The float32 forward pass runs
embedding, then per layer RMSNorm, RoPE (half-split pairs), causal
grouped-query attention and a SwiGLU MLP, then the final norm and the
output head (``reference.stack``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness.reference import HI, mm, rmsnorm, rope, round_fp8, stack

# published config.json key -> ModelConfig field
CONFIG_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
}
_ACTIVATIONS = {"silu": "swiglu"}
_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Dims:
    """The model's sizes, as the plain reference reads them."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c.get("hidden_act") != "silu" or c.get("tie_word_embeddings"):
            raise ValueError("the reference covers untied SwiGLU decoders")
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]))

    @classmethod
    def from_model(cls, cfg) -> "Dims":
        return cls(layers=cfg.num_layers, d_model=cfg.d_model,
                   heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                   head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
                   vocab=cfg.vocab_size, rope_theta=cfg.rope_theta,
                   rms_eps=cfg.rms_eps)


def check(cfg, config: dict) -> None:
    if cfg.attention != "gqa" or cfg.is_moe:
        raise ValueError(f"{cfg.arch_id}: the registry holds a "
                         f"{cfg.attention} {cfg.family} model, not dense GQA")
    if cfg.activation != _ACTIVATIONS[config["hidden_act"]] \
            or cfg.resolved_head_dim * cfg.num_heads != cfg.d_model:
        raise ValueError(f"{cfg.arch_id}: activation or head width "
                         "differs from the config file")


def shapes(d: Dims) -> dict:
    """Leaf shape and fan-in of every weight, as the engine lays them out."""
    L, D, H, K, hd, F, V = (d.layers, d.d_model, d.heads, d.kv_heads,
                            d.head_dim, d.d_ff, d.vocab)
    return {
        "embed": ((V, D), None),
        "final_norm": ((D,), "norm"),
        "lm_head": ((D, V), D),
        "layers": {
            "ln1": ((L, D), "norm"),
            "ln2": ((L, D), "norm"),
            "attn": {"wq": ((L, D, H, hd), D), "wk": ((L, D, K, hd), D),
                     "wv": ((L, D, K, hd), D), "wo": ((L, H, hd, D), H * hd)},
            "mlp": {"w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
                    "w_down": ((L, F, D), F)},
        },
    }


def attention(d, a: dict, ln, x):
    """``x`` (S, D) plus causal grouped-query attention over RMSNorm(x)."""
    s = x.shape[0]
    y = rmsnorm(ln, x, d.rms_eps)
    q = rope(mm(y, a["wq"]).reshape(s, d.heads, d.head_dim), d.rope_theta)
    k = rope(mm(y, a["wk"]).reshape(s, d.kv_heads, d.head_dim),
             d.rope_theta)
    v = mm(y, a["wv"]).reshape(s, d.kv_heads, d.head_dim)
    group = d.heads // d.kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k, precision=HI) \
        / jnp.sqrt(jnp.float32(d.head_dim))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, -1), v,
                     precision=HI)
    return x + mm(ctx.reshape(s, -1), a["wo"])


def _layer(d, fp8, x, lp):
    lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
    a, m = dict(lp["attn"]), dict(lp["mlp"])
    if fp8:
        a = {k: round_fp8(v) if k in _MATRICES else v for k, v in a.items()}
        m = {k: round_fp8(v) for k, v in m.items()}
    x = attention(d, a, lp["ln1"], x)
    y = rmsnorm(lp["ln2"], x, d.rms_eps)
    g = jax.nn.silu(mm(y, m["w_gate"]))
    u = mm(y, m["w_up"])
    return x + mm(g * u, m["w_down"]), None


@functools.partial(jax.jit, static_argnames=("d", "fp8"))
def logits(params, tokens, d, fp8: bool = False):
    """(S,) token ids -> (S, vocab) float32 next-token logits."""
    return stack(functools.partial(_layer, d, fp8), params, tokens,
                 d.rms_eps, fp8)
