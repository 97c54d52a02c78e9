"""Weight/activation sparsity profiling (paper §III-B, Table V, Eq. 1).

Two statistics, exactly as the paper defines them:

* **word sparsity** — fraction of quantized values that are exactly zero.
* **bit sparsity**  — fraction of 0 slots in the temporal-unary bitstream.
  Because the paper's outer-product GEMM unit finishes a step only when the
  *largest* magnitude in the tile has streamed out ("largest value bottlenecks
  GEMM compute"), the latency-relevant bit sparsity tracks the **maximum value
  per PE-array block** (the paper uses 32x32 blocks for LLaMA2 and per-feature
  -map maxima for CNNs):

      b_spa = 1 - mean_over_blocks( max|q|_block ) / Vmax

The per-element variant (mean|q| instead of block max) is also provided — it
upper-bounds the achievable savings and is what Table V's CNN numbers (~43%)
correspond to after feature-map averaging.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.quantization import Quantized, quantize, vmax

__all__ = [
    "SparsityStats",
    "word_sparsity",
    "bit_sparsity_elementwise",
    "bit_sparsity_blockmax",
    "profile_tensor",
    "profile_tree",
    "combine_stats",
]


@dataclasses.dataclass(frozen=True)
class SparsityStats:
    """Profiled sparsity for one tensor (or an aggregate)."""

    bits: int
    word: float          # fraction of zero words
    bit_elem: float      # element-wise bit sparsity (upper bound on savings)
    bit_blockmax: float  # block-max bit sparsity (Eq. 1 input)
    numel: int

    def dynamic_fraction(self) -> float:
        """Multiplier on worst-case latency (Eq. 1): 1 - b_spa."""
        return 1.0 - self.bit_blockmax


@partial(jax.jit)
def word_sparsity(q: jax.Array) -> jax.Array:
    """Fraction of exactly-zero quantized words.

    Args: ``q`` — integer quantization codes, any shape.
    Returns: scalar float32 in [0, 1] (dimensionless fraction).
    """
    return jnp.mean((q == 0).astype(jnp.float32))


@partial(jax.jit, static_argnames=("bits",))
def bit_sparsity_elementwise(q: jax.Array, bits: int) -> jax.Array:
    """Element-level bit sparsity: ``1 - mean|q| / L``.

    Args: ``q`` — integer codes; ``bits`` — operand width w, setting the
    unary stream length ``L = 2^(w-1)`` slots (paper convention; see
    ``unary.temporal_stream_len``).
    Returns: scalar float32 in [0, 1).  Upper-bounds the achievable Eq. 1
    saving — every lane terminating at its own magnitude — and is the
    ``dyn_floor`` statistic in the serve/planner cycle reports.
    """
    L = 2 ** (bits - 1)
    return 1.0 - jnp.mean(jnp.abs(q.astype(jnp.float32))) / L


@partial(jax.jit, static_argnames=("bits", "block"))
def bit_sparsity_blockmax(q: jax.Array, bits: int, block: int = 32) -> jax.Array:
    """1 - mean(max|q| per block x block tile) / Vmax  (paper's LLM method).

    Args: ``q`` — integer codes (flattened to 2-D over the trailing axis);
    ``bits`` — operand width w (``Vmax``-equivalent stream length
    ``L = 2^(w-1)``); ``block`` — PE-array tile edge (paper uses 32).
    Returns: scalar float32 in [0, 1) — the **Eq. 1 input**: the shared slot
    schedule finishes a step only when the largest magnitude per block has
    streamed out, so this is the latency-relevant statistic.  Padded
    all-zero blocks are masked out of the mean.
    """
    L = 2 ** (bits - 1)
    x = q[None, :] if q.ndim == 1 else q.reshape(-1, q.shape[-1])
    r, c = x.shape
    nr, nc = (r + block - 1) // block, (c + block - 1) // block
    x = jnp.pad(x, ((0, nr * block - r), (0, nc * block - c)))

    def band_max(i):
        # one (block, C) row band at a time: the block maxima of a stacked
        # weight never need a reshaped float copy of the whole tensor
        band = jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=0)
        band = jnp.abs(band.astype(jnp.int32)).reshape(block, nc, block)
        return jnp.max(band, axis=(0, 2))

    # bands past the last real row are never formed, so padded all-zero
    # blocks cannot bias the mean down
    blk_max = jax.lax.map(band_max, jnp.arange(nr)).astype(jnp.float32)
    return 1.0 - jnp.mean(blk_max) / L


def profile_tensor(x: jax.Array, bits: int, block: int = 32,
                   pre_quantized: bool = False) -> SparsityStats:
    """Quantize (unless already integer codes) and profile one tensor.

    Args: ``x`` — float tensor (or integer codes with ``pre_quantized``);
    ``bits`` — operand width w ∈ {2, 4, 8}; ``block`` — block-max tile edge.
    Returns: a :class:`SparsityStats` (all statistics dimensionless
    fractions; ``numel`` the element count used for size-weighted
    aggregation).  This is the statistic the serve cost tables and the
    mixed-precision planner (``eval/planner``) feed into Eq. 1.
    """
    if pre_quantized:
        q = jnp.asarray(x, jnp.int32)
    else:
        # Per-tensor quantization, as the paper profiles (block maxima are
        # measured against the tensor-global Vmax; per-channel scales would
        # renormalize every channel to its own max and hide bit sparsity).
        q = quantize(jnp.asarray(x), bits=bits, per_channel=False).values
    return SparsityStats(
        bits=bits,
        word=float(word_sparsity(q)),
        bit_elem=float(bit_sparsity_elementwise(q, bits)),
        bit_blockmax=float(bit_sparsity_blockmax(q, bits, block)),
        numel=int(q.size),
    )


def combine_stats(stats: list[SparsityStats]) -> SparsityStats:
    """Size-weighted aggregate across tensors (a model's layers).

    Args: ``stats`` — per-tensor stats at one shared ``bits``.
    Returns: one :class:`SparsityStats` whose fractions are
    ``numel``-weighted means (Table V's per-model numbers).
    """
    if not stats:
        raise ValueError("no stats to combine")
    bits = stats[0].bits
    total = sum(s.numel for s in stats)
    w = lambda f: sum(getattr(s, f) * s.numel for s in stats) / total
    return SparsityStats(bits=bits, word=w("word"), bit_elem=w("bit_elem"),
                         bit_blockmax=w("bit_blockmax"), numel=total)


def profile_tree(params, bits: int, block: int = 32,
                 min_ndim: int = 2) -> dict[str, SparsityStats]:
    """Profile every weight matrix in a parameter pytree.

    Skips vectors (norms, biases) by default — the paper profiles GEMM
    operands (conv / FC / attention projection weights).

    Returns ``{name: SparsityStats}`` keyed by the ``"/"``-joined
    parameter-tree path (``"layers/attn/wq"``) — the same names the
    backend runtime uses as GEMM *site* names (the naming contract in
    ``repro.backends.runtime``), so these stats join directly against
    recorded workloads and backend plans.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    out: dict[str, SparsityStats] = {}
    for path, leaf in flat:
        if not hasattr(leaf, "ndim") or leaf.ndim < min_ndim:
            continue
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[name] = profile_tensor(leaf, bits=bits, block=block)
    return out
