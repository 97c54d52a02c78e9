"""Serving path: page-allocator properties, paged-vs-contiguous
bit-exactness, scheduler/traffic determinism, continuous-vs-static gate.

Property tests use hypothesis when available and the local shim otherwise;
the 2x2-grid variant runs in a pinned subprocess (8 fake host devices) like
``test_grid.test_grid_multidevice``.
"""

import dataclasses
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
from repro.kernels import paged_attention as paged_lib
from repro.launch import serve as serve_lib
from repro.launch.mesh import single_device_mesh
from repro.models import model as model_lib
from repro.models.attention import _repeat_kv, naive_attention
from repro.serving import (PREFILL_LOGIT_TOL, OutOfPages, PageAllocator,
                           PagedKVCache, ServingEngine, TrafficConfig,
                           generate_trace, make_scheduler,
                           paged_vs_contiguous_probe)
from repro.serving.scheduler import Request


@pytest.fixture(scope="module")
def cfg():
    # fp32 end to end: every bit-exactness assertion below relies on the
    # paged and contiguous paths sharing one float path
    return dataclasses.replace(configs.get_smoke_config("llama3-8b"),
                               compute_dtype="float32",
                               param_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(0))


def _tcfg(seed=0, n=6, rate=1.0):
    """Small, fast trace: lengths sized for max_seq_len=32 test engines."""
    return TrafficConfig(num_requests=n, arrival_rate=rate,
                         prompt_short=(2, 5), prompt_long=(6, 10),
                         output_short=(2, 4), output_long=(5, 8),
                         p_long=0.4, seed=seed)


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq_len", 32)
    return ServingEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# Page allocator properties
# ---------------------------------------------------------------------------

class TestPageAllocator:
    @given(seed=st.integers(0, 10_000), num_pages=st.integers(2, 40))
    @settings(max_examples=25, deadline=None)
    def test_alloc_free_invariants(self, seed, num_pages):
        """Arbitrary alloc/free sequences: no aliasing across live owners,
        the reserved trash page is never handed out, and the free count is
        conserved at capacity minus what is live."""
        rng = np.random.default_rng(seed)
        alloc = PageAllocator(num_pages)
        live: dict[int, list[int]] = {}
        next_owner = 0
        for _ in range(60):
            if live and rng.random() < 0.4:
                owner = int(rng.choice(list(live)))
                alloc.free(live.pop(owner), owner)
            else:
                n = int(rng.integers(0, max(2, num_pages // 2)))
                if n > alloc.num_free:
                    with pytest.raises(OutOfPages):
                        alloc.alloc(n, next_owner)
                else:
                    live[next_owner] = alloc.alloc(n, next_owner)
                    next_owner += 1
            owned = [p for pages in live.values() for p in pages]
            assert len(owned) == len(set(owned)), "page aliased"
            assert all(p >= 1 for p in owned), "trash page handed out"
            assert alloc.num_free + len(owned) == alloc.capacity
            for owner, pages in live.items():
                assert all(alloc.owner_of(p) == owner for p in pages)

    def test_free_by_wrong_owner_asserts(self):
        alloc = PageAllocator(8)
        pages = alloc.alloc(2, "a")
        with pytest.raises(AssertionError):
            alloc.free(pages, "b")

    def test_double_allocate_request_rejected(self):
        cache = PagedKVCache(num_layers=1, num_kv_heads=1, head_dim=2,
                             num_pages=8, page_size=4, max_seq_len=16)
        cache.allocate(0, 5)
        with pytest.raises(ValueError):
            cache.allocate(0, 3)


# ---------------------------------------------------------------------------
# Paged cache reconstruction vs an append-only contiguous cache
# ---------------------------------------------------------------------------

class TestPagedReconstruction:
    @given(seed=st.integers(0, 10_000), page_size=st.integers(1, 5))
    @settings(max_examples=8, deadline=None)
    def test_block_table_walk_matches_contiguous(self, seed, page_size):
        """Interleaved prefill/append across requests (with a mid-sequence
        free + page reuse): walking each block table reconstructs exactly
        the values an append-only contiguous cache would hold."""
        rng = np.random.default_rng(seed)
        shape = dict(num_layers=2, num_kv_heads=2, head_dim=3)
        totals = [int(rng.integers(1, 3 * page_size + 1)) for _ in range(3)]
        num_pages = 1 + sum(-(-t // page_size) for t in totals)
        cache = PagedKVCache(num_pages=num_pages, page_size=page_size,
                             max_seq_len=4 * page_size, **shape)

        def vecs(*lead):
            return rng.normal(size=(*lead, 2, 2, 3)).astype(np.float32)

        ref_k: dict[int, list] = {}
        ref_v: dict[int, list] = {}
        for r, total in enumerate(totals):
            cache.allocate(r, total)
            s = int(rng.integers(1, total + 1))
            k = vecs(s).transpose(1, 0, 2, 3)   # (L, s, KVH, hd)
            v = vecs(s).transpose(1, 0, 2, 3)
            cache.write_prefill(r, jnp.asarray(k), jnp.asarray(v))
            ref_k[r], ref_v[r] = [k], [v]
        # free the middle request; a newcomer reuses its pages
        cache.free_request(1)
        cache.allocate(3, totals[1])
        s = max(1, totals[1] // 2)
        k = vecs(s).transpose(1, 0, 2, 3)
        v = vecs(s).transpose(1, 0, 2, 3)
        cache.write_prefill(3, jnp.asarray(k), jnp.asarray(v))
        ref_k[3], ref_v[3] = [k], [v]
        del ref_k[1], ref_v[1]
        lengths = {0: totals[0], 2: totals[2], 3: totals[1]}
        # interleaved single-token appends up to each reservation
        while any(cache.lengths[r] < lengths[r] for r in lengths):
            r = int(rng.choice([r for r in lengths
                                if cache.lengths[r] < lengths[r]]))
            k1, v1 = vecs(), vecs()          # (L, KVH, hd) single positions
            cache.append_token(r, jnp.asarray(k1), jnp.asarray(v1))
            ref_k[r].append(k1[:, None])
            ref_v[r].append(v1[:, None])
        for r in lengths:
            got_k, got_v = cache.gather_request(r)
            np.testing.assert_array_equal(got_k,
                                          np.concatenate(ref_k[r], axis=1))
            np.testing.assert_array_equal(got_v,
                                          np.concatenate(ref_v[r], axis=1))


# ---------------------------------------------------------------------------
# Paged decode bit-exactness vs the contiguous reference
# ---------------------------------------------------------------------------

class TestPagedBitExact:
    @pytest.mark.parametrize("page_size", [3, 8])
    def test_probe_bitexact(self, cfg, params, page_size):
        """Full-model probe: the engine's paged decode step equals the
        contiguous ``decode_step`` logits bit for bit at fp32, including at
        a page size that does not divide the prompt length; its bucketed
        prefill agrees with the contiguous prefill up to reassociation."""
        probe = paged_vs_contiguous_probe(cfg, params, prompt_len=5, steps=3,
                                          page_size=page_size)
        assert probe.decode == 0.0
        assert probe.prefill <= PREFILL_LOGIT_TOL

    @pytest.mark.parametrize("page_size", [3, 8])
    def test_ragged_paged_attention_exact(self, page_size):
        """Kernel-level: paged gather + masked attention over a ragged
        request mix equals the contiguous path exactly, even when the
        contiguous buffer's tail holds DIFFERENT garbage than the pool
        (masked scores underflow to exact zeros in fp32)."""
        rng = np.random.default_rng(3)
        kvh, heads, hd = 2, 4, 5
        lens = [7, 1, 12, page_size]            # page_size | 12? both sizes
        b = len(lens)
        cache = PagedKVCache(num_layers=1, num_kv_heads=kvh, head_dim=hd,
                             num_pages=1 + sum(-(-n // page_size)
                                               for n in lens),
                             page_size=page_size, max_seq_len=16)
        # contiguous reference at the gathered width: masked tail positions
        # contribute exact fp32 zeros whatever garbage they hold, but the
        # reduction *tree* must see the same width for bit-equality in eager
        # mode (within jit the engine probe also pins the unequal-width case)
        maxlen = cache.max_blocks * page_size
        contig_k = rng.normal(size=(b, maxlen, kvh, hd)).astype(np.float32)
        contig_v = rng.normal(size=(b, maxlen, kvh, hd)).astype(np.float32)
        btables = np.zeros((b, cache.max_blocks), np.int32)
        for i, n in enumerate(lens):
            cache.allocate(i, n)
            cache.write_prefill(i, jnp.asarray(contig_k[None, i, :n]),
                                jnp.asarray(contig_v[None, i, :n]))
            btables[i] = cache.block_table_row(i)
            contig_k[i, n:] = rng.normal(size=(maxlen - n, kvh, hd))
            contig_v[i, n:] = rng.normal(size=(maxlen - n, kvh, hd))
        # the gathered prefix is element-identical to the contiguous cache
        gk = np.asarray(paged_lib.gather_kv(cache.k_pool[0],
                                            jnp.asarray(btables)))
        for i, n in enumerate(lens):
            np.testing.assert_array_equal(gk[i, :n], contig_k[i, :n])
        valid = jnp.asarray(lens, jnp.int32)
        q = jnp.asarray(rng.normal(size=(b, 1, heads, hd)), jnp.float32)
        paged = paged_lib.paged_decode_attention(
            q, cache.k_pool[0], cache.v_pool[0], jnp.asarray(btables), valid,
            num_heads=heads)
        ref = naive_attention(q, _repeat_kv(jnp.asarray(contig_k), heads),
                              _repeat_kv(jnp.asarray(contig_v), heads),
                              causal=False, kv_valid_len=valid)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(ref))

    def test_gathered_kv_through_flash_attention(self):
        """The gathered pages ARE the contiguous tensor: pushing both
        through ``flash_attention`` (interpret mode) is bit-identical."""
        from repro.kernels.flash_attention import flash_attention
        rng = np.random.default_rng(7)
        kvh, hd, n = 2, 4, 10
        cache = PagedKVCache(num_layers=1, num_kv_heads=kvh, head_dim=hd,
                             num_pages=6, page_size=4, max_seq_len=16)
        k = rng.normal(size=(1, n, kvh, hd)).astype(np.float32)
        v = rng.normal(size=(1, n, kvh, hd)).astype(np.float32)
        cache.allocate(0, n)
        cache.write_prefill(0, jnp.asarray(k), jnp.asarray(v))
        gk, gv = cache.gather_request(0)   # (L=1, n, KVH, hd) == (B, S, H, d)
        q = jnp.asarray(rng.normal(size=(1, n, kvh, hd)), jnp.float32)
        out_paged = flash_attention(q, jnp.asarray(gk), jnp.asarray(gv),
                                    causal=True, interpret=True)
        out_ref = flash_attention(q, jnp.asarray(k), jnp.asarray(v),
                                  causal=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(out_paged),
                                      np.asarray(out_ref))


# ---------------------------------------------------------------------------
# Admission KV write: the donated device writer against write_prefill
# ---------------------------------------------------------------------------

class TestPromptWriter:
    @pytest.mark.parametrize("page_size", [4, 3])
    @pytest.mark.parametrize("row", [0, 2])
    @pytest.mark.parametrize("width,length", [(8, 7), (16, 13)])
    def test_matches_write_prefill_in_place(self, width, length, row,
                                            page_size):
        """One jitted call puts positions < length of the call's row into
        the request's pages bit for bit as ``write_prefill`` does, leaves
        every other request's pages (and the trash page) as they were, and
        consumes the donated pools."""
        rng = np.random.default_rng(width * 100 + row * 10 + page_size)
        shape = dict(num_layers=2, num_kv_heads=2, head_dim=3)
        caches = [PagedKVCache(num_pages=24, page_size=page_size,
                               max_seq_len=32, **shape) for _ in range(2)]
        pool = rng.normal(size=caches[0].k_pool.shape).astype(np.float32)
        for c in caches:     # stale values everywhere, as in a served pool
            c.sync_pools(jnp.asarray(pool), jnp.asarray(pool + 1.0))
            c.allocate("other", 9)
            c.allocate("new", length + 4)    # pages past the prompt too
        k_call = rng.normal(size=(2, 3, width, 2, 3)).astype(np.float32)
        v_call = rng.normal(size=(2, 3, width, 2, 3)).astype(np.float32)
        eager, device = caches
        eager.write_prefill("new", jnp.asarray(k_call[:, row, :length]),
                            jnp.asarray(v_call[:, row, :length]))

        pages = device.pages_needed(length)
        ids = np.zeros(-(-width // page_size), np.int32)
        ids[:pages] = device.block_tables["new"][:pages]
        k_in, v_in = device.k_pool, device.v_pool
        write = jax.jit(paged_lib.write_prompt_kv, donate_argnums=(0, 1))
        device.sync_pools(*write(k_in, v_in, jnp.asarray(k_call),
                                 jnp.asarray(v_call), row, length, ids))
        device.lengths["new"] = length

        assert k_in.is_deleted() and v_in.is_deleted()
        for got, want in zip(device.gather_request("new"),
                             eager.gather_request("new")):
            np.testing.assert_array_equal(got, want)
        mine = set(device.block_tables["new"][:pages])
        for p in range(device.num_pages):
            if p not in mine:
                np.testing.assert_array_equal(
                    np.asarray(device.k_pool[:, p]), pool[:, p])
                np.testing.assert_array_equal(
                    np.asarray(device.v_pool[:, p]), pool[:, p] + 1.0)
        np.testing.assert_array_equal(np.asarray(device.k_pool),
                                      np.asarray(eager.k_pool))
        np.testing.assert_array_equal(np.asarray(device.v_pool),
                                      np.asarray(eager.v_pool))

    def test_one_compile_per_bucket(self, cfg, params):
        """Prompts of many lengths in two prefill buckets compile the
        admission writer once per bucket, the run's first admission
        included, and a second run compiles none."""
        from repro.serving import spans as spans_lib
        from repro.serving.traffic import TrafficRequest
        lens = [5, 6, 7, 8, 9, 11, 13, 16]      # buckets 8 and 16
        trace = tuple(TrafficRequest(req_id=i, arrival_step=i,
                                     prompt_len=n, output_len=2)
                      for i, n in enumerate(lens))
        # a pool size no other engine of the process has compiled for
        eng = _engine(cfg, params, num_pages=31)
        compiles = []
        for _ in range(2):
            rec = spans_lib.SpanRecorder()
            eng.run(trace, spans=rec)
            writes = [s for s in rec.dump()["spans"]
                      if s["name"] == "kv.write_prefill"]
            assert len(writes) == len(lens)
            compiles.append([w["counts"].get("compiles", 0) for w in writes])
        assert compiles[0] == [1, 0, 0, 0, 1, 0, 0, 0]
        assert compiles[1] == [0] * len(lens)


# ---------------------------------------------------------------------------
# Schedulers: admission rules + the continuous-beats-static gate
# ---------------------------------------------------------------------------

class TestSchedulers:
    def _cache(self, num_pages=9, page_size=4):
        return PagedKVCache(num_layers=1, num_kv_heads=1, head_dim=2,
                            num_pages=num_pages, page_size=page_size,
                            max_seq_len=32)

    @staticmethod
    def _req(req_id, arrival, total):
        from repro.serving.traffic import TrafficRequest
        return Request(spec=TrafficRequest(req_id=req_id,
                                           arrival_step=arrival,
                                           prompt_len=total - 1,
                                           output_len=1))

    def test_static_admits_only_into_empty_batch(self):
        sched = make_scheduler("static", 2)
        waiting = [self._req(0, 0, 4), self._req(1, 0, 4)]
        assert len(sched.admissions(0, waiting, 0, self._cache())) == 2
        assert sched.admissions(0, waiting, 1, self._cache()) == []

    def test_fifo_head_of_line_blocks(self):
        """A head request that cannot reserve its pages blocks later ones
        (deterministic FIFO) even if they would fit."""
        sched = make_scheduler("continuous", 4)
        cache = self._cache(num_pages=3)      # 2 allocatable pages
        waiting = [self._req(0, 0, 12), self._req(1, 0, 4)]   # needs 3 vs 1
        assert sched.admissions(0, waiting, 0, cache) == []

    def test_not_yet_arrived_requests_wait(self):
        sched = make_scheduler("continuous", 4)
        waiting = [self._req(0, 5, 4)]
        assert sched.admissions(0, waiting, 0, self._cache()) == []
        assert len(sched.admissions(5, waiting, 0, self._cache())) == 1

    def test_engine_rejects_impossible_requests(self, cfg, params):
        eng = _engine(cfg, params, max_seq_len=16)
        bad = TrafficConfig(num_requests=1, prompt_short=(20, 20),
                            output_short=(9, 9), p_long=0.0, seed=0)
        with pytest.raises(ValueError, match="max_seq_len"):
            eng.run(generate_trace(bad))

    def test_continuous_beats_static(self, cfg, params):
        """The tentpole gate: on one seeded trace, continuous batching gets
        >= static throughput and >= occupancy; on the float path both
        schedulers generate identical per-request token streams."""
        eng = _engine(cfg, params)
        trace = generate_trace(_tcfg(n=6, rate=1.5))
        rc = eng.run(trace, "continuous")
        rs = eng.run(trace, "static")
        assert rc.requests == rs.requests == len(trace)
        assert rc.throughput_tok_per_step >= rs.throughput_tok_per_step
        assert rc.occupancy >= rs.occupancy
        assert rc.latency_p99 <= rs.latency_p99
        assert rc.request_tokens == rs.request_tokens
        assert rc.tokens == sum(r.output_len for r in trace)

    def test_page_pressure_queues_but_completes(self, cfg, params):
        """With a pool too small to co-run everything, admission stalls on
        pages but every request still completes (conservative reservation:
        no mid-decode out-of-pages)."""
        trace = generate_trace(_tcfg(n=5, rate=3.0))
        biggest = max(-(-r.total_len // 4) for r in trace)
        eng = _engine(cfg, params, num_pages=1 + biggest + 1)
        rep = eng.run(trace, "continuous")
        assert rep.requests == len(trace)
        admits = {e[2]: e[0] for e in rep.events if e[1] == "admit"}
        assert len(admits) == len(trace)


# ---------------------------------------------------------------------------
# Determinism: traffic, schedule, metrics
# ---------------------------------------------------------------------------

class TestDeterminism:
    def test_same_seed_same_trace(self):
        assert generate_trace(_tcfg(seed=3)) == generate_trace(_tcfg(seed=3))

    def test_different_seed_different_trace(self):
        assert generate_trace(_tcfg(seed=0)) != generate_trace(_tcfg(seed=1))

    def test_same_seed_same_schedule_and_metrics(self, cfg, params):
        """Two full serves of the same seeded trace produce identical
        join/evict event streams, latencies, tokens and energy."""
        eng = _engine(cfg, params)
        trace = generate_trace(_tcfg(seed=4, n=5))
        a = eng.run(trace, "continuous")
        b = eng.run(trace, "continuous")
        assert a.to_dict() == b.to_dict()

    def test_different_seed_different_schedule(self, cfg, params):
        eng = _engine(cfg, params)
        a = eng.run(generate_trace(_tcfg(seed=0, n=5)), "continuous")
        b = eng.run(generate_trace(_tcfg(seed=9, n=5)), "continuous")
        assert a.events != b.events


# ---------------------------------------------------------------------------
# Engine parity with the one-shot serve driver + backend/grid execution
# ---------------------------------------------------------------------------

class TestEngineParity:
    def test_single_request_matches_generate(self, cfg, params):
        """A lone request served through the paged engine emits exactly the
        greedy tokens ``launch.serve.generate`` produces for its prompt."""
        from repro.serving.traffic import TrafficRequest
        spec = TrafficRequest(req_id=0, arrival_step=0, prompt_len=6,
                              output_len=5)
        eng = _engine(cfg, params)
        rep = eng.run((spec,), "continuous")
        prompt = jnp.asarray(eng.prompt_tokens(spec)[None])
        ref = serve_lib.generate(cfg, params, single_device_mesh(), prompt,
                                 spec.output_len)
        assert rep.request_tokens[0] == tuple(int(t) for t in
                                              np.asarray(ref)[0])

    def test_backend_execution_flat_vs_1x1_grid(self, cfg, params):
        """Under tubgemm execution, a (1,1) PE-array grid serves the trace
        with exactly the flat backend's tokens and metrics (GridBackend is
        bit-exact vs its single-unit design)."""
        trace = generate_trace(_tcfg(n=3))
        flat = _engine(cfg, params, backend="tubgemm", bits=4).run(trace)
        grid = _engine(cfg, params, backend="tubgemm", bits=4,
                       grid=(1, 1)).run(trace)
        assert flat.request_tokens == grid.request_tokens
        assert flat.events == grid.events
        assert flat.throughput_tok_per_step == grid.throughput_tok_per_step


SERVING_GRID_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np
from repro import configs
import jax
from repro.models import model as model_lib
from repro.serving import ServingEngine, TrafficConfig, generate_trace

cfg = dataclasses.replace(configs.get_smoke_config("llama3-8b"),
                          compute_dtype="float32", param_dtype="float32")
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
trace = generate_trace(TrafficConfig(
    num_requests=3, arrival_rate=1.0, prompt_short=(2, 5),
    prompt_long=(6, 10), output_short=(2, 4), output_long=(5, 8),
    p_long=0.4, seed=0))
kw = dict(max_batch=3, page_size=4, max_seq_len=32, backend="tubgemm",
          bits=4)
flat = ServingEngine(cfg, params, **kw).run(trace)
engine = ServingEngine(cfg, params, grid=(2, 2), **kw)
# weights split over the grid the way its GEMMs split them: (L, K, N)
w_up = engine._exec_params["layers"]["mlp"]["w_up"]
assert len(w_up.sharding.device_set) == 4
assert w_up.addressable_shards[0].data.shape == (2, 32, 96), \
    w_up.addressable_shards[0].data.shape
assert engine._exec_params["embed"].sharding.is_fully_replicated
grid = engine.run(trace)
assert grid.requests == len(trace), grid.requests
assert flat.request_tokens == grid.request_tokens, (flat.request_tokens,
                                                    grid.request_tokens)
assert flat.events == grid.events
print("SERVING_GRID_2X2_OK")
"""


def test_serving_grid_2x2_subprocess():
    """On a 2x2 PE-array grid (8 fake host devices), the paged serving loop
    under sharded tubgemm execution, with its weights split over the grid,
    generates exactly the flat backend's token streams and schedule."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu",
           "JAX_DISABLE_MOST_OPTIMIZATIONS": "1",
           "JAX_COMPILATION_CACHE_DIR": os.path.abspath(".jax_cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    res = subprocess.run([sys.executable, "-c", SERVING_GRID_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert "SERVING_GRID_2X2_OK" in res.stdout, \
        f"{res.stdout}\n{res.stderr}"
