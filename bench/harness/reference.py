"""The plain float32 reference of the served models, and its control.

Straight ``jax.numpy`` over the weights the benchmark made: embedding,
then per layer RMSNorm, RoPE (half-split pairs), causal grouped-query
attention and a SwiGLU MLP, then the final norm and the output head.  No
kernel, cache, batching or backend scope of the program is used, and none
of its code is imported.  Every product runs at ``Precision.HIGHEST``
(on a TPU a float32 product at default precision is computed in bfloat16
passes).  Layers are cast to float32 one at a time inside the scan, so the
reference holds no float32 copy of the whole model.

The control (``fp8=True``) is the same pass with every weight matrix
rounded to float8 e4m3 under a power-of-two scale per matrix: the nearest
precision below the configuration's bfloat16 weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def round_fp8(w):
    """``w`` rounded to e4m3 (4 exponent, 3 mantissa bits), per-tensor
    power-of-two scale that keeps its largest value in range."""
    amax = jnp.max(jnp.abs(w))
    scale = jnp.exp2(jnp.floor(jnp.log2(240.0 / jnp.maximum(amax, 1e-30))))
    return jax.lax.reduce_precision(w * scale, exponent_bits=4,
                                    mantissa_bits=3) / scale


def _rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, hd) rotated by positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _mm(x, w):
    """``x`` (S, K) times ``w`` flattened to (K, N)."""
    return jnp.matmul(x, w.reshape(x.shape[-1], -1), precision=_HI)


def _layer(d, fp8, x, lp):
    lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
    a, m = dict(lp["attn"]), dict(lp["mlp"])
    if fp8:
        a = {k: round_fp8(v) if k in _MATRICES else v for k, v in a.items()}
        m = {k: round_fp8(v) for k, v in m.items()}
    s = x.shape[0]
    y = _rmsnorm(lp["ln1"], x, d.rms_eps)
    q = _rope(_mm(y, a["wq"]).reshape(s, d.heads, d.head_dim),
              d.rope_theta)
    k = _rope(_mm(y, a["wk"]).reshape(s, d.kv_heads, d.head_dim),
              d.rope_theta)
    v = _mm(y, a["wv"]).reshape(s, d.kv_heads, d.head_dim)
    group = d.heads // d.kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k, precision=_HI) \
        / jnp.sqrt(jnp.float32(d.head_dim))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, -1), v,
                     precision=_HI)
    x = x + _mm(ctx.reshape(s, -1), a["wo"])
    y = _rmsnorm(lp["ln2"], x, d.rms_eps)
    g = jax.nn.silu(_mm(y, m["w_gate"]))
    u = _mm(y, m["w_up"])
    return x + _mm(g * u, m["w_down"]), None


@functools.partial(jax.jit, static_argnames=("d", "fp8"))
def logits(params, tokens, d, fp8: bool = False):
    """(S,) token ids -> (S, vocab) float32 next-token logits."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(functools.partial(_layer, d, fp8), x,
                        params["layers"])
    x = _rmsnorm(params["final_norm"].astype(jnp.float32), x, d.rms_eps)
    head = params["lm_head"].astype(jnp.float32)
    if fp8:
        head = round_fp8(head)
    return _mm(x, head)


@jax.jit
def gaps(ref_logits, chosen):
    """Per row, how far the logit of ``chosen`` lies below the row's best."""
    at = jnp.take_along_axis(ref_logits, chosen[:, None], axis=1)[:, 0]
    return jnp.max(ref_logits, axis=1) - at


@jax.jit
def first_choice(lg):
    return jnp.argmax(lg, axis=1).astype(jnp.int32)
