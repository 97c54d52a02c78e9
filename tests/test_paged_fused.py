"""Fused page-walk decode attention: differential conformance + hot path.

The fused kernel's claim is *oracle-equivalence without materialization*:
walking the block table page-by-page (online softmax in the Pallas kernel,
oracle-shaped softmax in the XLA lowering) must stay within
``FUSED_LOGIT_TOL`` of ``paged_decode_attention`` everywhere, and the
serving engine's sampled token streams must be *identical* on seeded
traces — including under low-bit per-row activation quantization, where
any systematic numeric drift in the attention path gets amplified into
argmax flips.  This module holds that claim differentially:

* kernel-level fused-vs-gather parity across page sizes {3, 4, 8}, GQA
  ratios {1, 2, 4}, batch 1..max and ragged length mixes (len-1,
  page-boundary, post-evict page reuse), for both the XLA lowering and
  the Pallas kernel in interpret mode (hypothesis when available, the
  local shim otherwise);
* early-exit evidence: K pages past the batch's live high-water mark are
  never read (NaN poison stays un-observed);
* the bf16 dtype-schedule regression: the XLA lowering must mirror the
  oracle's cast points, not silently run at higher precision;
* engine-level stream identity fused vs gather (float and per-row
  tubgemm paths), batched vs per-request prefill admission parity at one
  fixed prefill batch, and the shared bounded prefill-fn cache;
* Eq.-1 energy pinned against the event stream (admission charges
  prefill exactly once; the first token never costs a decode tick);
* an 8-fake-device (1,1)-grid subprocess parity run, mirroring
  ``test_packed.test_packed_grid_multidevice``.
"""

import dataclasses
import functools
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # CI image has no hypothesis; use the local shim
    from _hypothesis_fallback import given, settings, strategies as st

from repro import configs
from repro.analysis import source_lint
from repro.kernels import paged_attention_fused as fused_lib
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import common as common_lib, model as model_lib
from repro.serving import (FUSED_LOGIT_TOL, PagedKVCache, ServingEngine,
                           TrafficConfig, fused_vs_gather_probe,
                           generate_trace)
from repro.serving import engine as engine_lib

#: kernel-level differential tolerance: the XLA lowering matches the oracle
#: elementwise (reduction association is the only freedom); the Pallas
#: online softmax re-associates more aggressively.
KERNEL_TOL = 2e-5


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(configs.get_smoke_config("llama3-8b"),
                               compute_dtype="float32",
                               param_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(0))


def _case(seed, *, batch, page_size, kvh, heads, hd, max_blocks, lengths,
          dtype=jnp.float32):
    """Random pools + shuffled (non-contiguous) block tables."""
    assert len(lengths) == batch
    num_pages = 1 + batch * max_blocks
    rng = np.random.default_rng(seed)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool_shape = (num_pages, page_size, kvh, hd)
    pool_k = jax.random.normal(k1, pool_shape).astype(dtype)
    pool_v = jax.random.normal(k2, pool_shape).astype(dtype)
    q = jax.random.normal(k3, (batch, 1, heads, hd)).astype(dtype)
    pages = rng.permutation(np.arange(1, num_pages))  # page 0 = trash
    bt = jnp.asarray(pages.reshape(batch, max_blocks), jnp.int32)
    return q, pool_k, pool_v, bt, jnp.asarray(lengths, jnp.int32)


def _diff(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# 1. kernel-level differential conformance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("page_size", [3, 4, 8])
@pytest.mark.parametrize("gqa", [1, 2, 4])
def test_fused_matches_oracle_page_gqa(impl, page_size, gqa):
    """Page sizes x GQA ratios x a ragged length mix incl. len-1 and exact
    page boundaries, against the gather oracle."""
    heads, kvh, hd = 4, 4 // gqa, 8
    max_blocks = 5
    lengths = [1, page_size, page_size + 1, min(3 * page_size + 2,
                                                max_blocks * page_size)]
    args = _case(page_size * 10 + gqa, batch=4, page_size=page_size, kvh=kvh,
                 heads=heads, hd=hd, max_blocks=max_blocks, lengths=lengths)
    ref = paged_decode_attention(*args, num_heads=heads)
    got = fused_lib.fused_paged_decode_attention(
        *args, num_heads=heads, impl=impl, interpret=(impl == "pallas"))
    assert got.shape == ref.shape and got.dtype == ref.dtype
    assert _diff(got, ref) <= KERNEL_TOL


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("batch", [1, 2, 3, 4])
def test_fused_matches_oracle_batch(impl, batch):
    """Batch 1..max with per-request ragged lengths."""
    heads, kvh, hd, page_size, max_blocks = 8, 2, 16, 4, 4
    lengths = [1 + (3 * i) % (max_blocks * page_size) for i in range(batch)]
    args = _case(100 + batch, batch=batch, page_size=page_size, kvh=kvh,
                 heads=heads, hd=hd, max_blocks=max_blocks, lengths=lengths)
    ref = paged_decode_attention(*args, num_heads=heads)
    got = fused_lib.fused_paged_decode_attention(
        *args, num_heads=heads, impl=impl, interpret=(impl == "pallas"))
    assert _diff(got, ref) <= KERNEL_TOL


@settings(deadline=None, max_examples=20)
@given(seed=st.integers(min_value=0, max_value=10_000),
       page_size=st.sampled_from([3, 4, 8]),
       gqa=st.sampled_from([1, 2, 4]),
       batch=st.integers(min_value=1, max_value=4))
def test_fused_matches_oracle_property(seed, page_size, gqa, batch):
    """Random lengths/pages/grouping: fused stays within tolerance."""
    heads, hd = 4, 8
    max_blocks = -(-24 // page_size)
    lengths = [1 + ((seed + 7 * i) % (max_blocks * page_size))
               for i in range(batch)]
    args = _case(seed, batch=batch, page_size=page_size,
                 kvh=heads // gqa, heads=heads, hd=hd, max_blocks=max_blocks,
                 lengths=lengths)
    ref = paged_decode_attention(*args, num_heads=heads)
    got = fused_lib.fused_paged_decode_attention(*args, num_heads=heads,
                                                 impl="xla")
    assert _diff(got, ref) <= KERNEL_TOL


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_post_evict_page_reuse(impl):
    """Block tables from a real allocate/free/allocate cycle: a freed
    request's pages are reused out of order by its successor."""
    page_size, kvh, heads, hd = 4, 2, 4, 8
    cache = PagedKVCache(num_layers=1, num_kv_heads=kvh, head_dim=hd,
                         num_pages=9, page_size=page_size, max_seq_len=16)
    rng = np.random.default_rng(7)
    cache.allocate(0, 9)    # 3 pages
    cache.allocate(1, 7)    # 2 pages
    cache.free_request(0)
    cache.allocate(2, 11)   # 3 pages, reusing request 0's freed pages
    for rid, n in ((1, 7), (2, 11)):
        k = rng.standard_normal((1, n, kvh, hd)).astype(np.float32)
        v = rng.standard_normal((1, n, kvh, hd)).astype(np.float32)
        cache.write_prefill(rid, jnp.asarray(k), jnp.asarray(v))
    bt = jnp.asarray(np.stack([cache.block_table_row(1),
                               cache.block_table_row(2)]), jnp.int32)
    lengths = jnp.asarray([7, 11], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(3), (2, 1, heads, hd))
    args = (q, cache.k_pool[0], cache.v_pool[0], bt, lengths)
    ref = paged_decode_attention(*args, num_heads=heads)
    got = fused_lib.fused_paged_decode_attention(
        *args, num_heads=heads, impl=impl, interpret=(impl == "pallas"))
    assert _diff(got, ref) <= KERNEL_TOL


def test_fused_xla_early_exit_never_reads_dead_k_pages():
    """K pages past the batch's live high-water mark carry NaN poison; the
    chunked walk (pages_per_chunk=1) must stop before touching them."""
    heads, kvh, hd, page_size, max_blocks = 4, 2, 8, 4, 8
    lengths = [5, 7]  # high-water mark: 2 pages per request
    args = _case(11, batch=2, page_size=page_size, kvh=kvh, heads=heads,
                 hd=hd, max_blocks=max_blocks, lengths=lengths)
    q, pool_k, pool_v, bt, lens = args
    live_pages = np.unique(np.asarray(bt)[:, :2])
    dead = np.setdiff1d(np.arange(pool_k.shape[0]), live_pages)
    poisoned_k = pool_k.at[jnp.asarray(dead)].set(jnp.nan)
    clean = fused_lib.fused_paged_decode_attention(
        q, pool_k, pool_v, bt, lens, num_heads=heads, impl="xla",
        pages_per_chunk=1)
    got = fused_lib.fused_paged_decode_attention(
        q, poisoned_k, pool_v, bt, lens, num_heads=heads, impl="xla",
        pages_per_chunk=1)
    assert np.array_equal(np.asarray(got), np.asarray(clean))
    assert np.isfinite(np.asarray(got)).all()


def test_fused_bf16_mirrors_oracle_dtype_schedule():
    """Under bf16 compute the oracle rounds K/V and the softmax weights to
    bf16 mid-path; the XLA lowering must mirror those cast points (same
    output dtype, bf16-level agreement), not run at silent fp32 — the
    regression that flipped per-row-quantized token streams."""
    heads, kvh, hd, page_size, max_blocks = 4, 2, 8, 4, 4
    args = _case(21, batch=3, page_size=page_size, kvh=kvh, heads=heads,
                 hd=hd, max_blocks=max_blocks, lengths=[1, 6, 13],
                 dtype=jnp.bfloat16)
    ref = paged_decode_attention(*args, num_heads=heads)
    got = fused_lib.fused_paged_decode_attention(*args, num_heads=heads,
                                                 impl="xla")
    assert got.dtype == ref.dtype == jnp.bfloat16
    # elementwise ops match the oracle bit-for-bit; only f32 reduction
    # association can differ, which the final bf16 rounding absorbs
    assert _diff(got, ref) <= 2 * float(jnp.finfo(jnp.bfloat16).eps)


#: the default-scale outputs of both lowerings on one seeded case, as the
#: kernel gave them before it took ``scale=``
DEFAULT_SCALE_DIGESTS = {
    "xla": "d1807bf0d7a076792cc6195cad55a52bd7ba06de08769a5fcb8bf3b68f5c12d8",
    "pallas": "1260a149a606f2884da081e5d931d30c5a4d338e577f055182adc7645df4e6c8",
}


def _scale_case():
    return _case(11, batch=3, page_size=4, kvh=2, heads=8, hd=16,
                 max_blocks=5, lengths=[7, 20, 1])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_default_scale_is_bit_identical_to_before(impl):
    import hashlib
    got = fused_lib.fused_paged_decode_attention(
        *_scale_case(), num_heads=8, impl=impl, interpret=(impl == "pallas"))
    assert hashlib.sha256(np.asarray(got).tobytes()).hexdigest() \
        == DEFAULT_SCALE_DIGESTS[impl]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("scale", [0.015625, 0.3])
def test_fused_configured_scale_matches_oracle(impl, scale):
    """A configured score scale (granite-4.0-h's 1/64, and one that is no
    power of two) against the gather oracle given the same scale."""
    args = _scale_case()
    ref = paged_decode_attention(*args, num_heads=8, scale=scale)
    got = fused_lib.fused_paged_decode_attention(
        *args, num_heads=8, impl=impl, interpret=(impl == "pallas"),
        scale=scale)
    assert _diff(got, ref) <= KERNEL_TOL
    default = paged_decode_attention(*args, num_heads=8)
    assert _diff(ref, default) > 100 * KERNEL_TOL


@pytest.mark.parametrize("impl", ["gather", "xla", "pallas"])
def test_layer_indexed_write_and_walk_match_the_layer_slice(impl):
    """For every layer of a stacked pool, the token write and the page walk
    at a (traced) layer index equal the single-layer form on that layer's
    pool, bit for bit; the write leaves every other layer as it was."""
    from repro.kernels.paged_attention import write_kv_token
    layers, page_size, kvh, heads, hd = 3, 4, 2, 4, 8
    q, _, _, bt, pos = _case(21, batch=3, page_size=page_size, kvh=kvh,
                             heads=heads, hd=hd, max_blocks=4,
                             lengths=[0, 5, 11])
    keys = jax.random.split(jax.random.PRNGKey(22), 4)
    shape = (layers, 1 + 3 * 4, page_size, kvh, hd)
    pool_k = jax.random.normal(keys[0], shape)
    pool_v = jax.random.normal(keys[1], shape)
    new_k = jax.random.normal(keys[2], (3, kvh, hd))
    new_v = jax.random.normal(keys[3], (3, kvh, hd))
    if impl == "gather":
        walk = functools.partial(paged_decode_attention, num_heads=heads)
    else:
        walk = functools.partial(fused_lib.fused_paged_decode_attention,
                                 num_heads=heads, impl=impl,
                                 interpret=(impl == "pallas"))

    @jax.jit
    def stacked(layer):
        pk = write_kv_token(pool_k, bt, pos, new_k, page_size, layer)
        pv = write_kv_token(pool_v, bt, pos, new_v, page_size, layer)
        return pk, pv, walk(q, pk, pv, bt, pos + 1, layer)

    @jax.jit
    def one(pk, pv):
        pk = write_kv_token(pk, bt, pos, new_k, page_size)
        pv = write_kv_token(pv, bt, pos, new_v, page_size)
        return pk, pv, walk(q, pk, pv, bt, pos + 1)

    for layer in range(layers):
        pk, pv, got = stacked(jnp.int32(layer))
        ref_k, ref_v, ref = one(pool_k[layer], pool_v[layer])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        for new, ref_pool, pool in ((pk, ref_k, pool_k), (pv, ref_v, pool_v)):
            np.testing.assert_array_equal(np.asarray(new[layer]),
                                          np.asarray(ref_pool))
            rest = np.array([i for i in range(layers) if i != layer])
            np.testing.assert_array_equal(np.asarray(new[rest]),
                                          np.asarray(pool[rest]))


def test_fused_rejects_bad_shapes_and_impl():
    args = _case(5, batch=2, page_size=4, kvh=2, heads=4, hd=8,
                 max_blocks=2, lengths=[3, 5])
    with pytest.raises(ValueError, match="impl"):
        fused_lib.fused_paged_decode_attention(*args, num_heads=4,
                                               impl="cuda")
    with pytest.raises(ValueError, match="divide"):
        fused_lib.fused_paged_decode_attention(*args, num_heads=3)
    q_bad = jnp.zeros((2, 2, 4, 8))
    with pytest.raises(ValueError, match="B, 1, H"):
        fused_lib.fused_paged_decode_attention(q_bad, *args[1:], num_heads=4)
    with pytest.raises(ValueError, match="L, P, page"):  # a layer, 4-D pools
        fused_lib.fused_paged_decode_attention(*args, 0, num_heads=4)


# ---------------------------------------------------------------------------
# 2. modeled traffic
# ---------------------------------------------------------------------------

def test_bytes_moved_model():
    """Fused traffic scales with live history at KV width; gather with the
    padded pool at query width."""
    fused = fused_lib.fused_decode_bytes_moved(
        [1, 8, 9], page_size=4, num_kv_heads=2, head_dim=64)
    # ceil(1/4)+ceil(8/4)+ceil(9/4) = 1+2+3 pages, K and V, f32
    assert fused == 2 * 6 * 4 * 2 * 64 * 4
    gather = fused_lib.gather_decode_bytes_moved(
        batch=3, max_blocks=16, page_size=4, num_kv_heads=2, num_heads=8,
        head_dim=64)
    assert gather == 2 * 3 * 16 * 4 * 8 * 64 * 4
    # the acceptance regime: B=8, 512 of 1024 context, page 4 -> >= 4x
    full = fused_lib.gather_decode_bytes_moved(
        batch=8, max_blocks=256, page_size=4, num_kv_heads=2, num_heads=8,
        head_dim=64)
    walk = fused_lib.fused_decode_bytes_moved(
        [512] * 8, page_size=4, num_kv_heads=2, head_dim=64)
    assert full / walk >= 4.0


# ---------------------------------------------------------------------------
# 3. engine-level stream identity + probes
# ---------------------------------------------------------------------------

def _run(cfg, params, attention, *, tcfg=None, scheduler="continuous", **kw):
    trace = generate_trace(tcfg or TrafficConfig(num_requests=8,
                                                 arrival_rate=1.0, seed=0))
    eng = ServingEngine(cfg, params, max_batch=4, page_size=8,
                        max_seq_len=64, attention=attention, **kw)
    return eng.run(trace, scheduler)


def test_engine_fused_vs_gather_streams_float(cfg, params):
    rf = _run(cfg, params, "fused")
    rg = _run(cfg, params, "gather")
    assert rf.request_tokens == rg.request_tokens
    assert rf.events == rg.events


def test_engine_fused_vs_gather_streams_per_row_quantized(cfg, params):
    """A CPU pin at smoke widths: per-row act quant over tubgemm@4
    amplifies any systematic attention drift into token flips.  (At
    published widths on a TPU the two lowerings' bf16 rounding alone flips
    tokens, so ``serve traffic`` reports this identity without gating.)"""
    with common_lib.activation_scaling("per-row"):
        rf = _run(cfg, params, "fused", backend="tubgemm", bits=4,
                  unit_n=64, num_units=64)
        rg = _run(cfg, params, "gather", backend="tubgemm", bits=4,
                  unit_n=64, num_units=64)
    assert rf.request_tokens == rg.request_tokens


def test_fused_vs_gather_probe_within_tol(cfg, params):
    assert fused_vs_gather_probe(cfg, params) <= FUSED_LOGIT_TOL


def test_fused_vs_gather_probe_with_donated_pools(cfg, params):
    """Both engines' decode steps donate their pools (a donated pool read
    again raises): the probe hands the fused step copies and walks on from
    the gather step's pools, here at a page size that splits the prompt and
    for more steps than its default."""
    assert fused_vs_gather_probe(cfg, params, page_size=3,
                                 steps=5) <= FUSED_LOGIT_TOL


def test_fused_vs_gather_probe_pallas_interpret(cfg, params):
    """The Pallas kernel (interpret mode on CPU) through the whole engine
    decode step, against the gather oracle."""
    diff = fused_vs_gather_probe(cfg, params, attention_impl="pallas",
                                 batch=2, steps=2)
    assert diff <= FUSED_LOGIT_TOL


def test_engine_rejects_bad_attention_args(cfg, params):
    with pytest.raises(ValueError, match="attention must be"):
        ServingEngine(cfg, params, attention="flash")
    with pytest.raises(ValueError, match="attention_impl"):
        ServingEngine(cfg, params, attention_impl="cuda")


# ---------------------------------------------------------------------------
# 4. batched prefill admission + shared prefill cache
# ---------------------------------------------------------------------------

def test_batched_prefill_streams_identical_to_per_request(cfg, params):
    """Grouping same-step admissions into one bucketed prefill call must be
    invisible in every token and event."""
    tcfg = TrafficConfig(num_requests=10, arrival_rate=2.0, seed=3)
    rb = _run(cfg, params, "fused", tcfg=tcfg, batched_prefill=True)
    rs = _run(cfg, params, "fused", tcfg=tcfg, batched_prefill=False)
    assert rb.request_tokens == rs.request_tokens
    assert rb.events == rs.events
    assert rb.energy_uj == rs.energy_uj


@pytest.mark.parametrize("scheduler", ["continuous", "static"])
def test_prefill_calls_have_one_fixed_batch(cfg, params, scheduler):
    """Same-step admissions share one call per bucket, and every call is
    padded to ``(max_batch, bucket)``: XLA compiles each batch size
    differently, so a fixed batch keeps a request's KV and first token
    independent of which requests were admitted with it."""
    # every prompt in the one bucket of 8, so co-admissions share calls
    trace = generate_trace(TrafficConfig(
        num_requests=10, arrival_rate=2.0, prompt_short=(5, 8),
        prompt_long=(5, 8), seed=3))
    eng = ServingEngine(cfg, params, max_batch=4, page_size=8,
                        max_seq_len=64)
    shapes = []
    prefill = eng._prefill

    def spy(tokens):
        shapes.append(tokens.shape)
        return prefill(tokens)

    eng._prefill = spy
    report = eng.run(trace, scheduler)
    admits = [e for e in report.events if e[1] == "admit"]
    assert len({e[0] for e in admits}) < len(admits)   # shared steps exist
    assert len(shapes) < len(trace)                    # ... and share calls
    assert all(s[0] == eng.max_batch for s in shapes)


def test_prefill_cache_shared_across_engines(cfg, params):
    """Two engines with identical (cfg, scope, bucket) keys reuse one
    compiled prefill instead of recompiling per construction."""
    e1 = ServingEngine(cfg, params, max_batch=2, page_size=8, max_seq_len=64)
    e2 = ServingEngine(cfg, params, max_batch=4, page_size=4, max_seq_len=64)
    toks = jnp.zeros((1, 8), jnp.int32)
    e1._prefill(toks)
    key = e1._prefill_cache_key(8)
    fn = engine_lib._PREFILL_FNS[key]
    e2._prefill(toks)
    assert engine_lib._PREFILL_FNS[key] is fn  # same compiled entry
    assert e1._prefill_cache_key(8) == e2._prefill_cache_key(8)
    # the key tracks trace-time context: bucket and act-scale mode split it
    assert e1._prefill_cache_key(16) != key
    with common_lib.activation_scaling("per-row"):
        assert e1._prefill_cache_key(8) != key


def test_prefill_cache_bounded():
    base = dict(engine_lib._PREFILL_FNS)
    try:
        for i in range(engine_lib.PREFILL_CACHE_MAXSIZE + 7):
            engine_lib._prefill_cache_get(("test-bound", i), lambda: object())
        assert len(engine_lib._PREFILL_FNS) <= engine_lib.PREFILL_CACHE_MAXSIZE
    finally:
        engine_lib._PREFILL_FNS.clear()
        engine_lib._PREFILL_FNS.update(base)


# ---------------------------------------------------------------------------
# 5. Eq.-1 energy pinned against the event stream
# ---------------------------------------------------------------------------

def _single_request_report(cfg, params, output_len):
    trace = (engine_lib.TrafficRequest(req_id=0, arrival_step=0,
                                       prompt_len=5, output_len=output_len),)
    eng = ServingEngine(cfg, params, max_batch=2, page_size=8,
                        max_seq_len=32)
    return eng, eng.run(trace, "continuous")


def test_energy_single_request_prefill_only(cfg, params):
    """output_len=1: the one token comes off the prefill logits at
    admission — energy is EXACTLY one prefill, zero decode ticks."""
    eng, rep = _single_request_report(cfg, params, output_len=1)
    assert rep.tokens == 1
    assert rep.energy_uj == eng.energy.prefill_energy_uj(5)


def test_energy_single_request_one_decode_step(cfg, params):
    """output_len=2: one admission + one decode tick with one active slot —
    energy == prefill(P) + 1 decode token, no prefill double-count on the
    admission step."""
    eng, rep = _single_request_report(cfg, params, output_len=2)
    assert rep.tokens == 2
    expect = eng.energy.prefill_energy_uj(5) + eng.energy.decode_energy_uj(1)
    assert rep.energy_uj == expect


def test_energy_matches_event_stream(cfg, params):
    """Replaying the report's event stream reprices the whole trace: each
    admit charges its request's true prompt length once, each decode tick
    charges its active-slot count once."""
    trace = generate_trace(TrafficConfig(num_requests=8, arrival_rate=1.0,
                                         seed=5))
    eng = ServingEngine(cfg, params, max_batch=4, page_size=8,
                        max_seq_len=64)
    rep = eng.run(trace, "continuous")
    prompt_len = {r.req_id: r.prompt_len for r in trace}
    expect = sum(eng.energy.prefill_energy_uj(prompt_len[rid])
                 for _, kind, rid in rep.events if kind == "admit")
    # reconstruct per-step active counts from admit/evict events: a request
    # decodes on every step after its admission until its eviction step
    admit = {rid: at for at, kind, rid in rep.events if kind == "admit"}
    evict = {rid: at for at, kind, rid in rep.events if kind == "evict"}
    for step in range(rep.steps):
        n = sum(1 for rid in admit
                if admit[rid] < step <= evict[rid])
        expect += eng.energy.decode_energy_uj(n)
    assert rep.energy_uj == pytest.approx(expect, rel=0, abs=1e-9)


# ---------------------------------------------------------------------------
# 6. source-lint coverage of the fused kernel
# ---------------------------------------------------------------------------

def test_source_lint_covers_fused_kernel():
    """The float-accumulation rule sees fused-kernel names; the shipped
    kernel passes only because its fp32-softmax pragmas are present."""
    bad = ("import jax.numpy as jnp\n"
           "def _fused_decode_probe(a, b):\n"
           "    return jnp.einsum('ij,jk->ik', a, b)\n")
    findings = source_lint.lint_source(
        bad, rel="src/repro/kernels/paged_attention_fused.py")
    assert any(f.rule == "float-accumulation" for f in findings)
    path = os.path.join(os.path.dirname(__file__), "..", "src", "repro",
                        "kernels", "paged_attention_fused.py")
    with open(path) as fh:
        shipped = fh.read()
    assert not source_lint.lint_source(
        shipped, rel="src/repro/kernels/paged_attention_fused.py")
    assert shipped.count("analysis: allow-float-accumulation") >= 2


# ---------------------------------------------------------------------------
# 7. 8-fake-device (1,1)-grid subprocess parity
# ---------------------------------------------------------------------------

MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
from repro import configs
from repro.models import model as model_lib
from repro.serving import ServingEngine, TrafficConfig, generate_trace

cfg = dataclasses.replace(configs.get_smoke_config("llama3-8b"),
                          compute_dtype="float32", param_dtype="float32")
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
trace = generate_trace(TrafficConfig(num_requests=4, arrival_rate=1.0,
                                     seed=0))
kw = dict(max_batch=2, page_size=8, max_seq_len=64, backend="tubgemm",
          bits=4, unit_n=64, num_units=64, grid=(1, 1))
rf = ServingEngine(cfg, params, attention="fused", **kw).run(
    trace, "continuous")
rg = ServingEngine(cfg, params, attention="gather", **kw).run(
    trace, "continuous")
assert rf.request_tokens == rg.request_tokens, (rf.request_tokens,
                                                rg.request_tokens)
print("FUSED_GRID_OK", rf.tokens)
"""


def test_fused_grid_multidevice():
    """With 8 fake host devices and a (1,1) shard grid, the fused decode
    path's token streams match the gather oracle's."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu",
           "JAX_DISABLE_MOST_OPTIMIZATIONS": "1",
           "JAX_COMPILATION_CACHE_DIR": os.path.abspath(".jax_cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    res = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert "FUSED_GRID_OK" in res.stdout, \
        f"missing FUSED_GRID_OK\n{res.stdout}\n{res.stderr}"
