"""Pallas TPU kernel: tiled low-precision integer GEMM with packed weights.

This is the TPU-native stand-in for the paper's PE array: the same (bm, bn)
output tiling with an inner loop over the common dimension K that the PPA
model prices (``core.ppa.DLAModel``), executed on the MXU with int8 inputs and
int32 accumulation.  INT4 and INT2 weights travel HBM->VMEM packed (2 or 4
values per byte) and are sign-extended in VMEM right before the MXU dot —
halving / quartering the weight-side HBM traffic, which is the memory-roofline
analog of the paper's "low precision cuts data movement" premise.

Grid: (M/bm, N/bn, K/bk) with the K axis innermost ("arbitrary" semantics);
the int32 accumulator lives in a VMEM scratch buffer and the output block is
written once on the final K step, optionally fused with the dequant epilogue
(per-output-channel scale, activations' per-tensor scale folded in).

Target: TPU v5e-class MXU (128x128); block defaults are MXU-aligned multiples
of 128.  Validated under ``interpret=True`` on CPU against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quant_gemm_kernel", "quant_gemm", "unpack_values", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bn, bk) — MXU-aligned


def unpack_values(packed: jax.Array, bits: int, axis: int = 0) -> jax.Array:
    """Sign-extend packed w-bit integers (int8 container) along ``axis``.

    Packing layout (see ops.pack_values): consecutive values along ``axis``
    share a byte, low nibble/crumb first.
    """
    if bits == 8:
        return packed
    if bits == 4:
        lo = jnp.left_shift(packed, 4) >> 4          # arithmetic shifts sign-extend
        hi = packed >> 4
        parts = [lo, hi]
    elif bits == 2:
        parts = []
        for s in (0, 2, 4, 6):
            crumb = jnp.left_shift(packed, 6 - s) >> 6
            parts.append(crumb)
    else:
        raise ValueError(f"unsupported bits={bits}")
    stacked = jnp.stack(parts, axis=axis + 1)        # (..., packed_dim, P, ...)
    shape = list(packed.shape)
    shape[axis] = shape[axis] * len(parts)
    return stacked.reshape(shape)


def quant_gemm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                      bits: int, n_k: int, fuse_dequant: bool):
    """One (bm, bn) output tile; K-step ``pl.program_id(2)``."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                  # (bm, bk) int8
    w = unpack_values(w_ref[...], bits, axis=0)     # (bk, bn) int8
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if fuse_dequant:
            o_ref[...] = acc.astype(jnp.float32) * s_ref[...]
        else:
            o_ref[...] = acc


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "block", "fuse_dequant", "interpret"))
def quant_gemm(x: jax.Array, w_packed: jax.Array, scales: jax.Array | None = None,
               *, bits: int = 8, block: tuple[int, int, int] = DEFAULT_BLOCK,
               fuse_dequant: bool = False, interpret: bool = False) -> jax.Array:
    """``x:(M,K) int8 @ unpack(w_packed):(K,N) -> (M,N)`` int32 or fp32.

    ``w_packed`` is (K*bits//8, N) int8.  ``scales`` is (1, N) fp32 (weight
    per-channel x activation per-tensor, pre-folded) and is required when
    ``fuse_dequant`` — the kernel then emits fp32.
    """
    if x.dtype != jnp.int8 or w_packed.dtype != jnp.int8:
        raise TypeError("quant_gemm wants int8 operands (packed for w)")
    pack = 8 // bits
    bm, bn, bk = block
    if bk % pack:
        raise ValueError("bk must be divisible by the packing factor")
    m, kdim = x.shape
    n = w_packed.shape[1]
    if w_packed.shape[0] * pack != kdim:
        raise ValueError(
            f"K mismatch: x has K={kdim}, w_packed unpacks to {w_packed.shape[0] * pack}")

    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_packed, 0, bk // pack), 1, bn)
    if scales is None:
        scales = jnp.ones((1, n), jnp.float32)
    sp = _pad_to(scales.astype(jnp.float32).reshape(1, n), 1, bn)

    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(quant_gemm_kernel, bits=bits, n_k=grid[2],
                          fuse_dequant=fuse_dequant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // pack, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (mp, np_), jnp.float32 if fuse_dequant else jnp.int32),
        scratch_shapes=[_acc_scratch(bm, bn)],
        interpret=interpret,
    )(xp, wp, sp)
    return out[:m, :n]


def _acc_scratch(bm: int, bn: int):
    """int32 VMEM accumulator tile (interpret mode emulates it on CPU)."""
    return pltpu.VMEM((bm, bn), jnp.int32)
