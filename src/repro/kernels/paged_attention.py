"""Gather-based paged-KV decode attention.

The serving engine (``repro.serving``) keeps each request's KV history in
fixed-size *pages* of a preallocated pool — ``(num_pages, page_size, KVH,
head_dim)`` per layer — indexed through a per-request *block table* (a row of
page ids).  This module is the device-side read/write path over that layout:

* :func:`write_kv_token` scatters one new K (or V) vector per request into
  the page/slot its current length maps to;
* :func:`write_prompt_kv` scatters one admitted prompt's K and V, a row of
  the padded prefill call's outputs, into that request's pages;
* :func:`gather_kv` materializes the per-request view ``(B, max_blocks *
  page_size, KVH, head_dim)`` by gathering pool pages through the block
  table;
* :func:`paged_decode_attention` runs the gathered view through the exact
  same ``naive_attention`` math as the contiguous decode path in
  ``models/attention._gqa_fwd`` (same score widths, same mask construction,
  same softmax), so paged decode is **bit-exact** with the contiguous
  reference at fp32 — ``tests/test_serving.py`` pins this, including through
  the ``kernels/flash_attention`` reference.

Everything is functional (pools in, pools out) so the serving engine can jit
one decode step over the whole layer stack with ``lax.scan``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import _repeat_kv, naive_attention

__all__ = ["write_kv_token", "write_prompt_kv", "gather_kv",
           "paged_decode_attention"]


def write_kv_token(pool: jax.Array, block_table: jax.Array,
                   lengths: jax.Array, new: jax.Array,
                   page_size: int) -> jax.Array:
    """Scatter one new KV vector per request into its page pool.

    ``pool``: (num_pages, page_size, KVH, hd); ``block_table``: (B,
    max_blocks) int32 page ids; ``lengths``: (B,) int32 — the position the
    new token lands at; ``new``: (B, KVH, hd).  Requests that should not
    write (evicted slots) must point their block-table row at the reserved
    trash page (page 0, never allocated — see ``serving.paged_kv``), which
    absorbs their scatter without aliasing any live request's pages.
    """
    pages = jnp.take_along_axis(
        block_table, (lengths // page_size)[:, None], axis=1)[:, 0]
    slots = lengths % page_size
    return pool.at[pages, slots].set(new.astype(pool.dtype))


def write_prompt_kv(k_pool: jax.Array, v_pool: jax.Array, k_call: jax.Array,
                    v_call: jax.Array, row: jax.Array, length: jax.Array,
                    page_ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Scatter one prompt's K and V, for every layer, into its pages.

    Pools: (L, num_pages, page_size, KVH, hd); ``k_call``/``v_call``: (L,
    B, W, KVH, hd), the padded outputs of the prefill call that ran the
    prompt; ``row``: its row in that call; ``length``: its true length;
    ``page_ids``: (ceil(W / page_size),) int32, the pages holding its
    positions in order, padded with the trash page 0.  Positions
    ``< length`` of the row land in its pages; every other position of the
    pools keeps its value (the trash page's too), so the result equals
    ``PagedKVCache.write_prefill`` of the row's first ``length`` positions.
    ``row``, ``length`` and ``page_ids`` may be traced: a jitted writer
    compiles once per pool and call shape, and a caller that donates the
    pools gets them updated in place.
    """
    num_layers, _, page_size = k_pool.shape[:3]
    blocks = page_ids.shape[0]
    pos = jnp.arange(blocks * page_size).reshape(blocks, page_size)
    keep = (pos < length)[None, :, :, None, None]

    def write(pool, call):
        new = jax.lax.dynamic_index_in_dim(call, row, axis=1, keepdims=False)
        pad = blocks * page_size - new.shape[1]
        new = jnp.pad(new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        new = new.reshape(num_layers, blocks, page_size, *new.shape[2:])
        old = pool[:, page_ids]
        return pool.at[:, page_ids].set(
            jnp.where(keep, new.astype(pool.dtype), old))

    return write(k_pool, k_call), write(v_pool, v_call)


def gather_kv(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """(num_pages, page_size, ...) gathered to (B, max_blocks * page_size, ...)."""
    b, max_blocks = block_table.shape
    gathered = pool[block_table]           # (B, max_blocks, page_size, ...)
    return gathered.reshape(b, max_blocks * pool.shape[1], *pool.shape[2:])


def paged_decode_attention(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
                           block_table: jax.Array, kv_valid_len: jax.Array,
                           *, num_heads: int,
                           scale: float | None = None) -> jax.Array:
    """Single-token GQA decode attention over the paged KV pool.

    ``q``: (B, 1, H, hd); ``kv_valid_len``: (B,) — per-request valid history
    *including* the token written this step.  Positions past a request's
    valid length (page padding plus whatever the gathered pages carry beyond
    it) are masked to the same -1e30 the contiguous path uses, so the
    softmax rows match the contiguous cache bit-for-bit whenever the
    gathered width equals the contiguous cache width.  ``scale``: the
    score scale, by default ``1/sqrt(hd)``.
    """
    kc = gather_kv(pool_k, block_table)
    vc = gather_kv(pool_v, block_table)
    k_full = _repeat_kv(kc.astype(q.dtype), num_heads)
    v_full = _repeat_kv(vc.astype(q.dtype), num_heads)
    return naive_attention(q, k_full, v_full, causal=False,
                           kv_valid_len=kv_valid_len, scale=scale)
