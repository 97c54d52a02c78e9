"""The plain float32 reference's shared pieces, and its control's rounding.

Each architecture's forward pass lives in ``archs/<arch>.py`` and is built
from these: straight ``jax.numpy`` over the weights the benchmark made.  No
kernel, cache, batching or backend scope of the program is used, and none
of its code is imported.  Every product runs at ``Precision.HIGHEST`` (on a
TPU a float32 product at default precision is computed in bfloat16
passes).  Layers are cast to float32 one at a time inside the scan, so the
reference holds no float32 copy of the whole model.

The control (``fp8=True``) is the same pass with every weight matrix
rounded to float8 e4m3 under a power-of-two scale per matrix: the nearest
precision below the configuration's bfloat16 weights.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def round_fp8(w):
    """``w`` rounded to e4m3 (4 exponent, 3 mantissa bits), per-tensor
    power-of-two scale that keeps its largest value in range."""
    amax = jnp.max(jnp.abs(w))
    scale = jnp.exp2(jnp.floor(jnp.log2(240.0 / jnp.maximum(amax, 1e-30))))
    return jax.lax.reduce_precision(w * scale, exponent_bits=4,
                                    mantissa_bits=3) / scale


def rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x (S, heads, hd) rotated by positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def mm(x, w):
    """``x`` (S, K) times ``w`` flattened to (K, N)."""
    return jnp.matmul(x, w.reshape(x.shape[-1], -1), precision=HI)


def stack(layer, params, tokens, rms_eps, fp8):
    """Embedding, ``layer(x, lp) -> (x, None)`` scanned over the stacked
    ``params["layers"]``, final norm and untied output head."""
    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = rmsnorm(params["final_norm"].astype(jnp.float32), x, rms_eps)
    head = params["lm_head"].astype(jnp.float32)
    if fp8:
        head = round_fp8(head)
    return mm(x, head)


@jax.jit
def gaps(ref_logits, chosen):
    """Per row, how far the logit of ``chosen`` lies below the row's best."""
    at = jnp.take_along_axis(ref_logits, chosen[:, None], axis=1)[:, 0]
    return jnp.max(ref_logits, axis=1) - at


@jax.jit
def first_choice(lg):
    return jnp.argmax(lg, axis=1).astype(jnp.int32)
