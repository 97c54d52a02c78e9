"""Fused block-table paged-decode GQA attention (flash-style page walk).

The gather path (:mod:`repro.kernels.paged_attention`) materializes the
whole padded per-request KV view ``(B, max_blocks * page_size, KVH, hd)``
from the page pool every decode step — then ``_repeat_kv``-expands it
H/KVH-fold before ``naive_attention`` — O(max_blocks · page_size · H) HBM
traffic per request per layer regardless of how much history actually
exists.  This module fuses the page walk into the attention kernel:

* each request's block table is walked **page by page** with a flash-style
  online softmax (running max + denominator, fp32 accumulators), so no
  gathered KV copy ever exists;
* GQA is handled natively by grouping the H query heads per KV head
  (``q.reshape(KVH, H // KVH, hd)``) — the KV pages are contracted as
  stored, never repeated;
* per-request valid lengths are masked in-kernel (same ``-1e30`` fill the
  gather path uses, so masked weights underflow to exact fp32 zeros);
* pages past ``ceil(len / page_size)`` are skipped: the Pallas kernel
  clamps the block-table index map to the last valid page (identical
  consecutive block indices elide the copy) and gates the compute with
  ``pl.when``; the XLA lowering stops its ``lax.while_loop`` at the batch
  max — traffic drops to O(len · KVH) per request per layer.

Two interchangeable lowerings sit behind
:func:`fused_paged_decode_attention`:

* ``impl="pallas"`` — the Pallas TPU kernel (scalar-prefetched block
  table + lengths drive the page DMA), validated under ``interpret=True``
  on CPU like every kernel in this package;
* ``impl="xla"`` — a hybrid lowering as plain jax ops: the K/score side
  keeps the page walk (a jittable ``lax.while_loop`` over page *chunks*
  with a batch-wide dynamic early exit, so K pages past the batch's
  history are never read), while the softmax and the weighted-V product
  run at the gather oracle's exact widths and dtype-cast points (V read
  through one grouped KVH-width gather, never H-repeated).  This is the
  serving default on hosts without a TPU (the tier-1 CPU suite), where
  emulating the grid would cost more than it saves, and its oracle-shaped
  numerics are what keep low-bit per-row-quantized token streams
  identical to the gather path.

Online softmax (pallas) re-associates the reduction, and the XLA
lowering's chunked score writes can still reassociate f32 reductions, so
fused outputs are NOT guaranteed bit-exact against the gather oracle —
the contract is a gated max |Δ| (``tests/test_paged_fused.py``,
``repro.serving.fused_vs_gather_probe``) plus exact parity of the
sampled token streams on seeded traffic traces.

Target: TPU v5e-class MXU; validated under ``interpret=True`` on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_paged_decode_attention", "fused_decode_bytes_moved",
           "gather_decode_bytes_moved", "DEFAULT_PAGES_PER_CHUNK"]

#: pages gathered per ``lax.while_loop`` iteration of the XLA lowering —
#: large enough that the per-iteration dispatch amortizes, small enough
#: that the early exit still tracks the batch's actual history length.
DEFAULT_PAGES_PER_CHUNK = 8

_MASK = -1e30  # same fill as models.attention.naive_attention


def _scaled(scores, hd: int, scale: float | None):
    """Scores times ``scale``; by default divided by ``sqrt(hd)``."""
    if scale is None:
        return scores / jnp.sqrt(jnp.float32(hd))
    return scores * jnp.float32(scale)


def _check_shapes(q, pool_k, pool_v, block_table, num_heads):
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(f"q must be (B, 1, H, hd), got {q.shape}")
    if pool_k.shape != pool_v.shape or pool_k.ndim != 4:
        raise ValueError(f"pools must share (P, page, KVH, hd): "
                         f"{pool_k.shape} vs {pool_v.shape}")
    kvh = pool_k.shape[2]
    if q.shape[2] != num_heads or num_heads % kvh:
        raise ValueError(f"num_heads {num_heads} must match q heads "
                         f"{q.shape[2]} and divide by KV heads {kvh}")
    if block_table.shape[0] != q.shape[0]:
        raise ValueError(f"block_table batch {block_table.shape[0]} != "
                         f"q batch {q.shape[0]}")


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B, max_blocks), block table + lengths scalar-prefetched
# ---------------------------------------------------------------------------

def _fused_decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, page_size: int,
                         num_kv_heads: int, scale: float | None):  # analysis: allow-float-accumulation (fp32 online-softmax accumulators are the kernel's contract)
    """One (request, page) grid step of the online-softmax page walk."""
    b, j = pl.program_id(0), pl.program_id(1)
    n_pages = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = len_ref[b]
    n_blocks = (valid + page_size - 1) // page_size

    @pl.when(j < n_blocks)
    def _page():  # analysis: allow-float-accumulation (fp32 softmax accumulators)
        q = q_ref[0, 0].astype(jnp.float32)              # (H, hd)
        k = k_ref[0].astype(jnp.float32)                 # (page, KVH, hd)
        v = v_ref[0].astype(jnp.float32)
        h, hd = q.shape
        g = h // num_kv_heads
        qg = q.reshape(num_kv_heads, g, hd)
        s = _scaled(jnp.einsum("kgd,tkd->kgt", qg, k), hd, scale)
        tok = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, page_size), 2)
        s = jnp.where(tok < valid, s, _MASK)             # (KVH, G, page)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1)
        acc_ref[...] = (alpha[..., None] * acc_ref[...]
                        + jnp.einsum("kgt,tkd->kgd", p, v))
        m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _done():
        out = acc_ref[...] / l_ref[...][..., None]       # (KVH, G, hd)
        o_ref[0, 0] = out.reshape(o_ref.shape[2:]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_heads", "interpret", "scale"))
def _fused_decode_pallas(q, pool_k, pool_v, block_table, kv_valid_len, *,
                         num_heads: int, interpret: bool = False,
                         scale: float | None = None):
    batch, _, h, hd = q.shape
    _, page_size, kvh, _ = pool_k.shape
    max_blocks = block_table.shape[1]
    g = h // kvh

    def _page_index(b, j, bt_ref, len_ref):
        # clamp past-the-end steps to the last live page: consecutive
        # identical block indices elide the DMA, so skipped pages cost no
        # HBM traffic (their compute is gated off by pl.when above)
        n_blocks = (len_ref[b] + page_size - 1) // page_size
        return (bt_ref[b, jnp.minimum(j, n_blocks - 1)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, h, hd), lambda b, j, bt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, hd), _page_index),
            pl.BlockSpec((1, page_size, kvh, hd), _page_index),
        ],
        out_specs=pl.BlockSpec((1, 1, h, hd),
                               lambda b, j, bt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kvh, g), jnp.float32),      # running max
            pltpu.VMEM((kvh, g), jnp.float32),      # running denominator
            pltpu.VMEM((kvh, g, hd), jnp.float32),  # fp32 out accumulator
        ],
    )
    kernel = functools.partial(_fused_decode_kernel, page_size=page_size,
                               num_kv_heads=kvh, scale=scale)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, h, hd), q.dtype),
        interpret=interpret,
    )(jnp.asarray(block_table, jnp.int32),
      jnp.asarray(kv_valid_len, jnp.int32), q, pool_k, pool_v)


# ---------------------------------------------------------------------------
# XLA lowering: lax.while_loop over page chunks, batch-wide early exit
# ---------------------------------------------------------------------------

def _fused_decode_xla(q, pool_k, pool_v, block_table, kv_valid_len, *,  # analysis: allow-float-accumulation (fp32 softmax, dtype schedule mirrors the gather oracle)
                      num_heads: int,
                      pages_per_chunk: int = DEFAULT_PAGES_PER_CHUNK,
                      scale: float | None = None):
    """K-side page walk + oracle-shaped softmax, as plain jax ops.

    Scores are computed page-chunk by page-chunk through the block table
    (a ``lax.while_loop`` that stops at the batch's live-page high-water
    mark — K pages past any request's history are never read) into a
    full-width f32 buffer initialized to the mask fill.  The softmax and
    the weighted-V contraction then run at the oracle's exact widths and
    dtypes — same einsum operand dtypes, same f32 cast points, same
    ``w.astype(v.dtype)`` rounding before the V product — so every
    elementwise op matches ``paged_decode_attention`` bit-for-bit and only
    f32 reduction association can differ.  That is what keeps the sampled
    token streams identical to the gather path on the seeded traffic
    traces even under low-bit per-row activation quantization, where any
    systematic dtype mismatch gets amplified into argmax flips.

    V pages are read through one grouped (KVH-width, never H-repeated)
    gather so the contraction reduces in the oracle's order; the full
    O(len·KVH) two-sided walk is the Pallas kernel's job.
    """
    batch, _, h, hd = q.shape
    _, page_size, kvh, _ = pool_k.shape
    max_blocks = block_table.shape[1]
    g = h // kvh
    ppc = max(1, min(pages_per_chunk, max_blocks))
    n_chunks = -(-max_blocks // ppc)
    bt = jnp.pad(jnp.asarray(block_table, jnp.int32),
                 ((0, 0), (0, n_chunks * ppc - max_blocks)))  # trash page 0
    valid = jnp.asarray(kv_valid_len, jnp.int32)
    qg = q[:, 0].reshape(batch, kvh, g, hd)
    t_chunk = ppc * page_size
    width = max_blocks * page_size
    # chunks that contain at least one live token for some request
    stop = -(-jnp.max(-(-valid // page_size)) // ppc)

    def cond(state):
        return state[0] < stop

    def body(state):
        c, scores = state
        cols = jax.lax.dynamic_slice(bt, (0, c * ppc), (batch, ppc))
        k = pool_k[cols].astype(q.dtype).reshape(batch, t_chunk, kvh, hd)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, k).astype(jnp.float32)
        s = _scaled(s, hd, scale)
        tok = c * t_chunk + jnp.arange(t_chunk, dtype=jnp.int32)
        s = jnp.where(tok[None, None, None, :] < valid[:, None, None, None],
                      s, _MASK)
        scores = jax.lax.dynamic_update_slice(scores, s, (0, 0, 0, c * t_chunk))
        return c + 1, scores

    init = (jnp.int32(0),
            jnp.full((batch, kvh, g, n_chunks * t_chunk), _MASK, jnp.float32))
    _, scores = jax.lax.while_loop(cond, body, init)
    w = jax.nn.softmax(scores[..., :width], axis=-1)     # (B, KVH, G, S)
    vc = pool_v[block_table].reshape(batch, width, kvh, hd).astype(q.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", w.astype(vc.dtype), vc)
    return out.reshape(batch, h, hd)[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# dispatcher + modeled HBM traffic
# ---------------------------------------------------------------------------

def fused_paged_decode_attention(q, pool_k, pool_v, block_table,
                                 kv_valid_len, *, num_heads: int,
                                 impl: str = "auto", interpret: bool = False,
                                 pages_per_chunk: int = DEFAULT_PAGES_PER_CHUNK,
                                 scale: float | None = None):
    """Single-token fused GQA decode attention over the paged KV pool.

    Drop-in for :func:`repro.kernels.paged_attention.paged_decode_attention`
    (same signature and masking semantics) minus its materialization:
    ``q`` (B, 1, H, hd); pools (P, page_size, KVH, hd); ``block_table``
    (B, max_blocks) int32 page ids; ``kv_valid_len`` (B,) valid history
    *including* the token written this step (must be >= 1 per request —
    evicted slots point at the trash page with length 0, so the engine
    passes ``lengths + 1``).

    ``impl``: ``"pallas"`` (the TPU kernel; pass ``interpret=True`` on
    CPU), ``"xla"`` (the while-loop lowering), or ``"auto"`` — pallas iff
    the default jax backend is a TPU.  ``scale``: the score scale, by
    default ``1/sqrt(hd)``.
    """
    _check_shapes(q, pool_k, pool_v, block_table, num_heads)
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return _fused_decode_pallas(q, pool_k, pool_v, block_table,
                                    kv_valid_len, num_heads=num_heads,
                                    interpret=interpret, scale=scale)
    if impl == "xla":
        return _fused_decode_xla(q, pool_k, pool_v, block_table,
                                 kv_valid_len, num_heads=num_heads,
                                 pages_per_chunk=pages_per_chunk, scale=scale)
    raise ValueError(f"impl must be 'pallas', 'xla' or 'auto', got {impl!r}")


def gather_decode_bytes_moved(*, batch: int, max_blocks: int, page_size: int,
                              num_kv_heads: int, num_heads: int,
                              head_dim: int, dtype_bytes: int = 4) -> int:
    """Modeled KV bytes one gather-path decode step moves per layer.

    ``gather_kv`` reads every block-table page (live or trash) for K and V
    and ``_repeat_kv`` expands the gathered view to all H query heads, so
    the traffic scales with the pool's padded width and the *query* head
    count: O(max_blocks · page_size · H).
    """
    return (2 * batch * max_blocks * page_size * num_heads * head_dim
            * dtype_bytes)


def fused_decode_bytes_moved(lengths, *, page_size: int, num_kv_heads: int,
                             head_dim: int, dtype_bytes: int = 4) -> int:
    """Modeled KV bytes one fused decode step moves per layer.

    The page walk reads only ``ceil(len / page_size)`` pages per request,
    at KV-head width (queries are grouped, pages never repeated):
    O(len · KVH) per request.
    """
    pages = sum(-(-int(n) // page_size) for n in lengths)
    return 2 * pages * page_size * num_kv_heads * head_dim * dtype_bytes
