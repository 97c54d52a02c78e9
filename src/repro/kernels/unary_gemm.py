"""Pallas TPU kernels: the temporal-unary slot loops as tiled on-device GEMMs.

Two kernels, one per temporal design of the paper (§II):

* **tubGEMM** (``tub_gemm``) streams the A operand in *2-unary*: per
  outer-product step, ``|a| = 2*v1 + v0`` where ``v1`` gates
  ``L2 = 2^(w-2)`` weight-2 slots and the odd bit ``v0`` rides slot 0;
  B stays binary and is conditionally accumulated every slot.
* **tuGEMM** (``tu_gemm``) streams A in plain temporal-unary over
  ``L = 2^(w-1)`` slots; each 1-slot of A gates a full replay of B's own
  temporal stream into the output counters.  The replay sums to exactly
  ``sign(b) * |b| = b``, so the kernel folds it into one signed add of B per
  A-slot (the adder tree's total, bit-for-bit) while keeping the outer
  temporal schedule — the part that sets the cycle count — literal.

Both kernels execute their slot loop as a ``fori_loop`` inside each
(bm, bn, bk) tile, one conditional-add (masked MXU dot) per slot, so the
on-device schedule mirrors the hardware schedule the PPA model prices, while
the result stays bit-identical to binary int32 GEMM (the equivalence the
paper proves).

Structure mirrors ``quant_gemm.py``: grid (M/bm, N/bn, K/bk) with the K axis
innermost, an int32 VMEM scratch accumulator, and the output block written on
the final K step.  Validated under ``interpret=True`` against
``ref.tub_gemm_ref`` / ``ref.tu_gemm_ref`` and ``gemm_sims.bgemm_exact``.

Alongside the output the wrappers report the design's cycle count
(``K * 2^(w-2)`` for tubGEMM, ``K * (2^(w-1))^2`` for tuGEMM — the paper's WC
latency for the simulated unit, a host-side constant, not a device
measurement).  ``kernels.backends`` registers both as executable designs in
the ``gemm_sims`` registry so sweeps can cross-check simulator cycles against
kernel cycle reports.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quant_gemm import _acc_scratch, _pad_to

__all__ = [
    "tub_gemm_kernel",
    "tub_gemm",
    "tub_wc_cycles",
    "tu_gemm_kernel",
    "tu_gemm",
    "tu_wc_cycles",
    "DEFAULT_BLOCK",
]

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bn, bk) — MXU-aligned


def tub_wc_cycles(bits: int, common_dim: int) -> int:
    """Worst-case tubGEMM cycles for one GEMM with common dimension K.

    Args: ``bits`` — operand bit-width w; ``common_dim`` — K.
    Returns: cycles (dimensionless count; multiply by
    ``ppa.CLOCK_PERIOD_NS`` for ns): one pass of ``L2 = 2^(w-2)`` slots per
    outer-product step, ``K * L2``.  Equals ``wc_cycles("tubgemm", ...)``.
    """
    return common_dim * max(1, 2 ** (bits - 2))


def tu_wc_cycles(bits: int, common_dim: int) -> int:
    """Worst-case tuGEMM cycles for one GEMM with common dimension K.

    Args: ``bits`` — operand bit-width w; ``common_dim`` — K.
    Returns: cycles (dimensionless count; multiply by
    ``ppa.CLOCK_PERIOD_NS`` for ns): every one of A's ``L = 2^(w-1)`` slots
    replays B's full L-slot stream, per outer-product step — ``K * L^2``.
    Equals ``wc_cycles("tugemm", ...)``.
    """
    return common_dim * (2 ** (bits - 1)) ** 2


def tub_gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, bits: int, n_k: int):
    """One (bm, bn) output tile; K-step ``pl.program_id(2)``.

    Per K tile: decompose A into (v1, v0, sign) and run the 2-unary slot
    loop — slot t adds ``(2*[t < v1] + [t == 0]*v0) * sign @ B`` into the
    accumulator, exactly the conditional adder bank of the tubGEMM PE column.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.int32)                 # (bm, bk)
    b = b_ref[...]                                   # (bk, bn) int8
    mag = jnp.abs(a)
    sgn = jnp.sign(a)
    v1, v0 = mag // 2, mag % 2
    n_slots = max(1, 2 ** (bits - 2))

    def slot(t, acc):
        two_gate = 2 * (t < v1).astype(jnp.int32)    # weight-2 slots
        one_gate = jnp.where(t == 0, v0, 0)          # odd bit on slot 0
        pulses = (two_gate + one_gate) * sgn         # (bm, bk), |p| <= 3
        # int8 x int8 -> int32 is the MXU's native integer path (Mosaic
        # refuses an int32 x int32 dot); the pulses and B codes both fit.
        return acc + jax.lax.dot_general(
            pulses.astype(jnp.int8), b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    acc_ref[...] += jax.lax.fori_loop(0, n_slots, slot,
                                      jnp.zeros_like(acc_ref))

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def tub_gemm(a: jax.Array, b: jax.Array, *, bits: int = 8,
             block: tuple[int, int, int] = DEFAULT_BLOCK,
             interpret: bool = False) -> tuple[jax.Array, int]:
    """``a:(M,K) int8 codes @ b:(K,N) int8 -> ((M,N) int32, wc_cycles)``.

    ``a`` holds w-bit sign-magnitude-encodable codes (|a| <= 2^(w-1)-1, the
    symmetric-quantization range); ``b`` is plain int8.  Output is exactly
    ``bgemm_exact(a, b)`` — the point is the *schedule*, priced by
    ``core.ppa`` at ``tub_wc_cycles(bits, K)`` cycles.
    """
    if a.dtype != jnp.int8 or b.dtype != jnp.int8:
        raise TypeError("tub_gemm wants int8 operands")
    bm, bn, bk = block
    m, kdim = a.shape
    if b.shape[0] != kdim:
        raise ValueError(f"K mismatch: a has K={kdim}, b has K={b.shape[0]}")
    n = b.shape[1]

    ap = _pad_to(_pad_to(a, 0, bm), 1, bk)
    bp = _pad_to(_pad_to(b, 0, bk), 1, bn)
    mp, kp = ap.shape
    np_ = bp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(tub_gemm_kernel, bits=bits, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[_acc_scratch(bm, bn)],
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n], tub_wc_cycles(bits, kdim)


def tu_gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, bits: int, n_k: int):
    """One (bm, bn) output tile; K-step ``pl.program_id(2)``.

    Per K tile: decompose A into (magnitude, sign) and run the temporal slot
    loop — slot i adds ``[i < |a|] * sign @ B`` into the accumulator.  The
    masked dot is the adder-tree total of B's replayed temporal stream for
    that slot (the replay's counter sum is ``sign(b) * |b| = b``), so each
    loop iteration is one outer slot of the tuGEMM PE column, bit-for-bit.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.int32)                 # (bm, bk)
    b = b_ref[...]                                   # (bk, bn) int8
    mag = jnp.abs(a)
    sgn = jnp.sign(a)
    n_slots = 2 ** (bits - 1)

    def slot(i, acc):
        pulses = (i < mag).astype(jnp.int32) * sgn   # (bm, bk), |p| <= 1
        return acc + jax.lax.dot_general(
            pulses.astype(jnp.int8), b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    acc_ref[...] += jax.lax.fori_loop(0, n_slots, slot,
                                      jnp.zeros_like(acc_ref))

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def tu_gemm(a: jax.Array, b: jax.Array, *, bits: int = 8,
            block: tuple[int, int, int] = DEFAULT_BLOCK,
            interpret: bool = False) -> tuple[jax.Array, int]:
    """``a:(M,K) int8 codes @ b:(K,N) int8 -> ((M,N) int32, wc_cycles)``.

    ``a`` holds w-bit sign-magnitude-encodable codes (|a| <= 2^(w-1)-1, the
    symmetric-quantization range); ``b`` is plain int8.  Output is exactly
    ``tugemm_exact(a, b)`` (== binary int32 GEMM) — the point is the
    *schedule*, priced by ``core.ppa`` at ``tu_wc_cycles(bits, K)`` cycles.
    """
    if a.dtype != jnp.int8 or b.dtype != jnp.int8:
        raise TypeError("tu_gemm wants int8 operands")
    bm, bn, bk = block
    m, kdim = a.shape
    if b.shape[0] != kdim:
        raise ValueError(f"K mismatch: a has K={kdim}, b has K={b.shape[0]}")
    n = b.shape[1]

    ap = _pad_to(_pad_to(a, 0, bm), 1, bk)
    bp = _pad_to(_pad_to(b, 0, bk), 1, bn)
    mp, kp = ap.shape
    np_ = bp.shape[1]
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(tu_gemm_kernel, bits=bits, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[_acc_scratch(bm, bn)],
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n], tu_wc_cycles(bits, kdim)
