"""Pallas TPU kernel: fused unpack-and-contract GEMM over int32-word stores.

The decode-hot companion to :mod:`repro.core.packing`: weights travel
HBM->VMEM as the int32 words ``pack_codes`` emits (16 / 8 / 4 codes per
word at 2 / 4 / 8 bits — a 4–16x cut in weight-side HBM traffic vs the
float leaf) and are sign-extended *inside the tile loop*, right before the
MXU dot.  Neither the dequantized float matrix nor the full int8 code
matrix ever exists in HBM; per K-step only one ``(bk, bn)`` code tile
lives in VMEM.  The dequant epilogue (weight per-channel scales, with the
activations' scale folded in by the caller) runs once per output tile on
the final K step.

Same grid/accumulator scheme as :mod:`repro.kernels.quant_gemm` —
``(M/bm, N/bn, K/bk)`` with K innermost, int32 VMEM accumulator — so the
two kernels are drop-in comparable; the differential suite
(``tests/test_packed.py``) holds this kernel bit-exact against the
materializing reference and against every backend engine's
quantize-then-execute path.

Target: TPU v5e-class MXU; validated under ``interpret=True`` on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import packing
from repro.kernels.quant_gemm import _acc_scratch, _pad_to

__all__ = ["packed_gemm_kernel", "packed_gemm", "packed_matmul",
           "unpack_words", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bn, bk) — MXU-aligned


def unpack_words(words: jax.Array, bits: int) -> jax.Array:
    """Sign-extend a ``(words, n)`` int32-word tile to ``(words*cpw, n)``
    int32 codes (lane order per ``packing.pack_codes``: low lanes first).

    Static Python-int shift amounts only — this is the in-kernel unpack,
    traced inside ``pl.pallas_call``.
    """
    cpw = packing.codes_per_word(bits)
    parts = [jnp.left_shift(words, 32 - bits * (j + 1)) >> (32 - bits)
             for j in range(cpw)]
    stacked = jnp.stack(parts, axis=1)            # (words, cpw, n)
    return stacked.reshape(words.shape[0] * cpw, words.shape[1])


def packed_gemm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *,
                      bits: int, n_k: int, fuse_dequant: bool):
    """One (bm, bn) output tile; K-step ``pl.program_id(2)``."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the MXU: w-bit codes (w <= 8) fit int8
    w = unpack_words(w_ref[...], bits).astype(jnp.int8)   # (bk, bn) codes
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if fuse_dequant:
            o_ref[...] = acc.astype(jnp.float32) * s_ref[...]
        else:
            o_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("bits", "k", "block", "fuse_dequant", "interpret"))
def packed_gemm(x: jax.Array, w_words: jax.Array,
                scales: jax.Array | None = None, *, bits: int, k: int,
                block: tuple[int, int, int] = DEFAULT_BLOCK,
                fuse_dequant: bool = False,
                interpret: bool = False) -> jax.Array:
    """``x:(M,K) int8 @ unpack(w_words):(K,N) -> (M,N)`` int32 or fp32.

    ``w_words`` is the ``(ceil(K/cpw), N)`` int32 store ``pack_codes``
    emits for a (K, N) code matrix; ``k`` is the logical K (the padding
    lanes of the last word hold zero codes, which contract to exact
    zeros).  ``scales`` is (1, N) fp32, required when ``fuse_dequant``.
    """
    if x.dtype != jnp.int8:
        raise TypeError(f"packed_gemm wants int8 activations, got {x.dtype}")
    if w_words.dtype != jnp.int32:
        raise TypeError(
            f"packed_gemm wants an int32 word store, got {w_words.dtype}")
    cpw = packing.codes_per_word(bits)
    bm, bn, bk = block
    if bk % cpw:
        raise ValueError(f"bk={bk} must be a multiple of the {cpw} codes "
                         f"per word at {bits}-bit")
    m, kdim = x.shape
    n = w_words.shape[1]
    if kdim != k:
        raise ValueError(f"K mismatch: x has K={kdim}, store holds k={k}")
    if w_words.shape[0] != -(-k // cpw):
        raise ValueError(
            f"word-count mismatch: store has {w_words.shape[0]} words, "
            f"k={k} at {bits}-bit needs {-(-k // cpw)}")

    # bk is word-aligned (bk % cpw == 0), so padding K to bk also covers
    # the store's word-aligned length; the extra rows are zero codes.
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    wp = _pad_to(_pad_to(w_words, 0, bk // cpw), 1, bn)
    if scales is None:
        scales = jnp.ones((1, n), jnp.float32)
    sp = _pad_to(scales.astype(jnp.float32).reshape(1, n), 1, bn)

    mp, kp = xp.shape
    np_ = wp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(packed_gemm_kernel, bits=bits, n_k=grid[2],
                          fuse_dequant=fuse_dequant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // cpw, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct(
            (mp, np_), jnp.float32 if fuse_dequant else jnp.int32),
        scratch_shapes=[_acc_scratch(bm, bn)],
        interpret=interpret,
    )(xp, wp, sp)
    return out[:m, :n]


def packed_matmul(x: jax.Array, store: "packing.PackedQuantized", *,
                  block: tuple[int, int, int] = DEFAULT_BLOCK,
                  fuse_dequant: bool = True,
                  interpret: bool = False) -> jax.Array:
    """Contract int8 activation codes against a :class:`PackedQuantized`
    store without leaving the word domain.

    ``store`` must be a flat (non-grid, unstacked) 2-D-logical store —
    grid stores shard through ``GridBackend.execute``; stacked stores are
    sliced by the caller's scan.  With ``fuse_dequant`` the weight's
    per-channel scales apply in the epilogue (fold the activation scale
    into the fp32 result, as ``models/common._backend_matmul`` does).
    """
    if not packing.is_packed(store):
        raise TypeError(f"packed_matmul wants a PackedQuantized store, "
                        f"got {type(store).__name__}")
    if store.grid_x != 1:
        raise ValueError("grid stores execute through GridBackend; "
                         "packed_matmul wants a flat (grid_x=1) store")
    if store.packed.ndim != 2:
        raise ValueError(f"packed_matmul wants an unstacked store, got "
                         f"packed shape {store.packed.shape}")
    scales = store.scale.reshape(1, -1) if fuse_dequant else None
    return packed_gemm(x, store.packed, scales, bits=store.bits, k=store.k,
                       block=block, fuse_dequant=fuse_dequant,
                       interpret=interpret)
