"""Plain float32 reference forward pass for the dense GQA transformer.

Straight ``jax.numpy``: no kernels, no KV cache, no batching tricks, no
sharding, no backend scopes — the equations of one pre-norm decoder
(RMSNorm, RoPE, causal grouped-query attention, gated MLP) written out once
so that the serving path can be held against something that shares none of
its code.  Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32
matmul at default precision is computed in bfloat16 passes, which would make
the reference as coarse as the thing it checks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

__all__ = ["reference_logits"]

_HI = jax.lax.Precision.HIGHEST


def _rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (S, heads, hd) rotated by positions 0..S-1 (half-split pairing)."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def _act(cfg: ModelConfig, g):
    if cfg.activation == "swiglu":
        return jax.nn.silu(g)
    return jax.nn.gelu(g, approximate=True)


def _layer(cfg: ModelConfig, x, lp):
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    a = lp["attn"]
    y = _rmsnorm(lp["ln1"], x, cfg.rms_eps)
    q = _rope(jnp.einsum("sd,dhk->shk", y, a["wq"], precision=_HI),
              cfg.rope_theta)
    k = _rope(jnp.einsum("sd,dhk->shk", y, a["wk"], precision=_HI),
              cfg.rope_theta)
    v = jnp.einsum("sd,dhk->shk", y, a["wv"], precision=_HI)
    k = jnp.repeat(k, h // kvh, axis=1)
    v = jnp.repeat(v, h // kvh, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k, precision=_HI) / jnp.sqrt(
        jnp.float32(hd))
    s = x.shape[0]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, axis=-1), v,
                     precision=_HI)
    x = x + jnp.einsum("qhk,hkd->qd", ctx, a["wo"], precision=_HI)
    m = lp["mlp"]
    y = _rmsnorm(lp["ln2"], x, cfg.rms_eps)
    up = jnp.matmul(y, m["w_up"], precision=_HI)
    if "w_gate" in m:
        up = _act(cfg, jnp.matmul(y, m["w_gate"], precision=_HI)) * up
    else:
        up = _act(cfg, up)
    return x + jnp.matmul(up, m["w_down"], precision=_HI), None


@functools.partial(jax.jit, static_argnums=1)
def _forward(params, cfg: ModelConfig, tokens):
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.float32(cfg.d_model))
    x, _ = jax.lax.scan(lambda c, lp: _layer(cfg, c, lp), x,
                        params["layers"])
    x = _rmsnorm(params["final_norm"], x, cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.matmul(x, head, precision=_HI)
    if cfg.logit_softcap is not None:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def reference_logits(params, cfg: ModelConfig, tokens) -> jax.Array:
    """(S,) token ids -> (S, vocab) float32 next-token logits.

    Dense GQA decoders only (the family ``ServingEngine`` serves).  The
    whole sequence runs in one causal pass, so row ``i`` is the model's
    prediction after ``tokens[: i + 1]``.
    """
    if (cfg.attention != "gqa" or cfg.is_moe or cfg.ssm is not None
            or cfg.rwkv is not None):
        raise ValueError("reference_logits covers the dense GQA family, got "
                         f"family={cfg.family!r} attention={cfg.attention!r}")
    return _forward(params, cfg, jnp.asarray(tokens, jnp.int32))
