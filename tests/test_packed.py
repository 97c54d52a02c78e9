"""Bit-packed weight subsystem: differential conformance + plan replay.

The packed store's whole claim is *bit-identity*: a ``PackedQuantized``
leaf carries exactly the codes and scales ``quantize`` produces, so
executing from it — simulator, Pallas mirror, grid shard, serving engine —
must match the quantize-then-execute float path bit for bit at every
width.  This module holds that claim differentially:

* pack/unpack round-trip properties (hypothesis when available, the local
  shim otherwise): every signed ``bits``-wide code survives, odd and
  non-word-divisible lengths included, per-channel and per-row scales;
* packed-vs-float ``dense`` bit-identity across EVERY registered backend
  spec at bits {2, 4, 8}, plus the fused Pallas kernel vs a materializing
  int reference;
* (1,1)-grid in-process parity and a 2x2-grid subprocess parity run
  (pinned 8 fake host devices, like ``test_grid.test_grid_multidevice``);
* plan-replay regression: ``serve``'s plan evidence (tokens, drift,
  rel-RMSE, measured-cycle bounds) is identical packed vs unpacked;
* the stale-weight hazards: re-quantizing packed codes at a second width
  raises everywhere it could silently happen, and the analysis passes
  (``packed-materialize`` source rule, ``packed-width-mismatch`` plan
  rule) flag the static versions.
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

from repro import backends, configs
from repro.analysis import plan_lint, source_lint
from repro.backends.plan import BackendPlan, SiteAssignment
from repro.core import accounting, packing
from repro.core.quantization import quantize, quantize_per_row, vmax
from repro.eval import planner as planner_lib
from repro.kernels import packed_gemm as pk
from repro.launch import serve as serve_lib
from repro.launch.mesh import single_device_mesh
from repro.models import common, model as model_lib
from repro.serving import ServingEngine, TrafficConfig, generate_trace

#: every registered spec, stochastic ones pinned to a short stream
ALL_SPECS = tuple(
    name + (":16" if name == "ugemm_stochastic" else "")
    for name in backends.available())


def _resolve(spec, bits):
    kw = {"interpret": True} if spec.endswith("_pallas") else {}
    return backends.resolve(spec, bits=bits, **kw)


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(configs.get_smoke_config("llama3-8b"),
                               compute_dtype="float32",
                               param_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# 1. pack/unpack round-trip properties
# ---------------------------------------------------------------------------

class TestRoundTrip:

    @settings(max_examples=40, deadline=None)
    @given(bits=st.sampled_from([2, 4, 8]),
           k=st.integers(min_value=1, max_value=37),
           n=st.integers(min_value=1, max_value=9),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_full_signed_range_round_trips(self, bits, k, n, seed):
        # the whole signed range, including -2^(bits-1) (below the symmetric
        # quantizer's -vmax) — the word layout must not assume the quantizer
        rng = np.random.default_rng(seed)
        lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
        codes = jnp.asarray(rng.integers(lo, hi + 1, (k, n)), jnp.int8)
        words = packing.pack_codes(codes, bits)
        assert words.dtype == jnp.int32
        assert words.shape == (-(-k // packing.codes_per_word(bits)), n)
        back = packing.unpack_codes(words, bits, k)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(codes))

    @settings(max_examples=25, deadline=None)
    @given(bits=st.sampled_from([2, 4, 8]),
           k=st.integers(min_value=2, max_value=33),
           n=st.integers(min_value=1, max_value=8),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_pack_quantized_matches_quantize(self, bits, k, n, seed):
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(0, 1, (k, n)), jnp.float32)
        store = packing.pack_quantized(w, bits=bits)
        ref = quantize(w, bits=bits)
        np.testing.assert_array_equal(np.asarray(store.codes()),
                                      np.asarray(ref.values))
        np.testing.assert_array_equal(np.asarray(store.scale),
                                      np.asarray(ref.scale))
        np.testing.assert_array_equal(np.asarray(store.dequantize()),
                                      np.asarray(ref.dequantize()))

    @settings(max_examples=15, deadline=None)
    @given(bits=st.sampled_from([2, 4, 8]),
           seed=st.integers(min_value=0, max_value=2**31 - 1))
    def test_per_row_scales_round_trip(self, bits, seed):
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.normal(0, 1, (11, 6)), jnp.float32)
        q = quantize_per_row(w, bits=bits)
        store = packing.from_quantized(q)
        assert store.scale.shape == (11, 1)
        np.testing.assert_array_equal(np.asarray(store.dequantize()),
                                      np.asarray(q.dequantize()))

    def test_stacked_leaf_packs_per_slice(self, rng):
        # a scanned-layers leaf: every slice gets its own per-channel scales
        w = jnp.asarray(rng.normal(0, 1, (3, 10, 4)), jnp.float32)
        store = packing.pack_quantized(w, bits=4, k=10, n_out=4)
        ref = jax.vmap(lambda m: quantize(m, bits=4))(w)
        np.testing.assert_array_equal(np.asarray(store.codes()),
                                      np.asarray(ref.values))
        # lax.scan-style slicing keeps the aux consistent per layer
        leaves, treedef = jax.tree_util.tree_flatten(store)
        sliced = jax.tree_util.tree_unflatten(
            treedef, [l[1] for l in leaves])
        assert sliced.shape == (10, 4)
        np.testing.assert_array_equal(np.asarray(sliced.codes()),
                                      np.asarray(ref.values[1]))

    def test_multi_axis_k_and_tail(self, rng):
        # out-projection-shaped leaf: k folds (heads, head_dim)
        w = jnp.asarray(rng.normal(0, 1, (4, 8, 12)), jnp.float32)
        store = packing.pack_quantized(w, bits=4, k=32, n_out=12)
        assert store.shape == (4, 8, 12)
        flat = store.reshape(32, 12)
        assert flat.shape == (32, 12)
        ref = quantize(w.reshape(32, 12), bits=4)
        np.testing.assert_array_equal(np.asarray(flat.codes()),
                                      np.asarray(ref.values))
        with pytest.raises(ValueError, match="without mixing"):
            store.reshape(12, 32)

    def test_grid_shards_reassemble_to_full_codes(self, rng):
        # per-band packing (k=10 over 4 bands: ceil split, padded last band)
        w = jnp.asarray(rng.normal(0, 1, (10, 6)), jnp.float32)
        store = packing.pack_quantized(w, bits=4, grid_x=4)
        assert store.grid_x == 4
        ref = quantize(w, bits=4)
        np.testing.assert_array_equal(np.asarray(store.codes()),
                                      np.asarray(ref.values))
        np.testing.assert_array_equal(np.asarray(store.dequantize()),
                                      np.asarray(ref.dequantize()))

    def test_bad_widths_and_shapes_raise(self, rng):
        with pytest.raises(ValueError, match="packable widths"):
            packing.codes_per_word(3)
        w = jnp.asarray(rng.normal(0, 1, (6, 4)), jnp.float32)
        with pytest.raises(ValueError, match="not a stack"):
            packing.pack_quantized(w, bits=4, k=5, n_out=4)
        store = packing.pack_quantized(w, bits=4)
        with pytest.raises(ValueError, match="second width"):
            packing.pack_quantized(store, bits=2)


# ---------------------------------------------------------------------------
# 2. packed-vs-float dense bit-identity, every backend spec x {2, 4, 8}
# ---------------------------------------------------------------------------

class TestDenseBitIdentity:

    @pytest.mark.parametrize("spec", ALL_SPECS)
    @pytest.mark.parametrize("bits", [2, 4, 8])
    def test_packed_equals_float_path(self, rng, spec, bits):
        k, n = 24, 12  # small: the Pallas mirrors pad to their block
        w = jnp.asarray(rng.normal(0, 1, (k, n)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (3, k)), jnp.float32)
        backend = _resolve(spec, bits)
        store = packing.pack_quantized(w, bits=bits)
        with backends.use_backend(backend):
            ref = common.dense(w, x, name="w")
        with backends.use_backend(backend) as execution:
            got = common.dense(store, x, name="w")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        call = execution.calls[0]
        assert (call.k, call.n_out) == (k, n)

    def test_width_mismatch_raises(self, rng):
        w = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (2, 16)), jnp.float32)
        store = packing.pack_quantized(w, bits=8)
        with backends.use_backend("tubgemm", bits=4):
            with pytest.raises(ValueError, match="packed-width-mismatch"):
                common.dense(store, x, name="w")

    def test_unmatched_plan_site_dequantizes(self, rng):
        # a site the plan leaves unmatched runs FLOAT from dequantized codes
        w = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (2, 16)), jnp.float32)
        store = packing.pack_quantized(w, bits=4)
        plan = BackendPlan(sites=(SiteAssignment(
            pattern="other/*", design="tubgemm", bits=4),))
        with backends.use_plan(plan):
            got = common.dense(store, x, name="w")
        want = x @ store.dequantize()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_quant_kernel_path_refuses_packed(self, rng, cfg):
        w = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (2, 16)), jnp.float32)
        store = packing.pack_quantized(w, bits=4)
        qcfg = dataclasses.replace(cfg, quant_bits=4, quant_kernel=True)
        with pytest.raises(TypeError, match="second time"):
            common.dense(store, x, qcfg, name="w")


# ---------------------------------------------------------------------------
# 3. fused Pallas kernel vs the materializing reference
# ---------------------------------------------------------------------------

class TestFusedKernel:

    @pytest.mark.parametrize("bits", [2, 4, 8])
    def test_bit_exact_vs_materializing_reference(self, rng, bits):
        m, k, n = 5, 37, 11  # odd everything: padding + last-word lanes
        v = vmax(bits)
        x = jnp.asarray(rng.integers(-v, v + 1, (m, k)), jnp.int8)
        codes = jnp.asarray(rng.integers(-v, v + 1, (k, n)), jnp.int8)
        words = packing.pack_codes(codes, bits)
        got = pk.packed_gemm(x, words, bits=bits, k=k, block=(8, 8, 32),
                             interpret=True)
        ref = jnp.matmul(x.astype(jnp.int32), codes.astype(jnp.int32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_fused_dequant_epilogue(self, rng):
        w = jnp.asarray(rng.normal(0, 1, (20, 6)), jnp.float32)
        store = packing.pack_quantized(w, bits=4)
        v = vmax(4)
        x = jnp.asarray(rng.integers(-v, v + 1, (3, 20)), jnp.int8)
        got = pk.packed_matmul(x, store, block=(8, 8, 16), interpret=True)
        acc = jnp.matmul(x.astype(jnp.int32),
                         store.codes().astype(jnp.int32))
        ref = acc.astype(jnp.float32) * store.scale.reshape(1, -1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_kernel_validates_inputs(self, rng):
        store = packing.pack_quantized(
            jnp.ones((8, 4), jnp.float32), bits=4)
        with pytest.raises(TypeError, match="int8 activations"):
            pk.packed_gemm(jnp.ones((2, 8), jnp.float32), store.packed,
                           bits=4, k=8)
        with pytest.raises(ValueError, match="multiple of"):
            pk.packed_gemm(jnp.ones((2, 8), jnp.int8), store.packed,
                           bits=4, k=8, block=(8, 8, 12))
        grid_store = packing.pack_quantized(
            jnp.ones((8, 4), jnp.float32), bits=4, grid_x=2)
        with pytest.raises(ValueError, match="flat"):
            pk.packed_matmul(jnp.ones((2, 8), jnp.int8), grid_store)


# ---------------------------------------------------------------------------
# 4. pack_weights + whole-model / grid parity
# ---------------------------------------------------------------------------

def _uniform_plan(cfg, params, design="tubgemm", bits=4):
    sites = planner_lib.discover_sites(cfg, params)
    return BackendPlan(sites=tuple(
        SiteAssignment(pattern=s.name, design=design, bits=bits,
                       m=s.m, k=s.k, n_out=s.n_out, count=s.count)
        for s in sites))


class TestModelParity:

    def test_pack_weights_uniform_bits_forward_bit_identical(self, cfg,
                                                             params):
        packed = backends.pack_weights(cfg, params, bits=4)
        tokens = jnp.zeros((2, 4), jnp.int32)
        with backends.use_backend("tubgemm", bits=4):
            ref, _ = model_lib.forward(params, cfg, tokens)
        with backends.use_backend("tubgemm", bits=4):
            got, _ = model_lib.forward(packed, cfg, tokens)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_pack_weights_plan_forward_bit_identical(self, cfg, params):
        plan = _uniform_plan(cfg, params)
        packed = backends.pack_weights(cfg, params, plan)
        widths = packing.packed_widths(packed)
        assert widths and set(widths.values()) == {4}
        tokens = jnp.zeros((2, 4), jnp.int32)
        with backends.use_plan(plan):
            ref, _ = model_lib.forward(params, cfg, tokens)
        with backends.use_plan(plan):
            got, _ = model_lib.forward(packed, cfg, tokens)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_one_by_one_grid_parity(self, cfg, params):
        flat = _uniform_plan(cfg, params)
        gplan = backends.GridPlan(units_x=1, units_y=1, aggregate=flat,
                                  shards=())
        packed = backends.pack_weights(cfg, params, gplan)
        tokens = jnp.zeros((2, 4), jnp.int32)
        with backends.use_plan(gplan):
            ref, _ = model_lib.forward(params, cfg, tokens)
        with backends.use_plan(gplan):
            got, _ = model_lib.forward(packed, cfg, tokens)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_pack_weights_wants_exactly_one_selector(self, cfg, params):
        with pytest.raises(ValueError, match="exactly one"):
            backends.pack_weights(cfg, params)
        plan = _uniform_plan(cfg, params)
        with pytest.raises(ValueError, match="exactly one"):
            backends.pack_weights(cfg, params, plan, bits=4)

    def test_pack_weights_width_conflict_raises(self, cfg, params):
        packed = backends.pack_weights(cfg, params, bits=8)
        # matching width: packed leaves pass through untouched
        again = backends.pack_weights(cfg, packed, bits=8)
        assert packing.packed_widths(again) == packing.packed_widths(packed)
        with pytest.raises(ValueError, match="packed-width-mismatch"):
            backends.pack_weights(cfg, packed, bits=4)

    def test_store_report_reductions(self, cfg, params):
        rep4 = accounting.packed_store_report(
            backends.pack_weights(cfg, params, bits=4))
        rep8 = accounting.packed_store_report(
            backends.pack_weights(cfg, params, bits=8))
        assert rep4.packed_sites > 0
        assert rep4.packed_sites == rep8.packed_sites
        # 4-bit: 8 codes/word -> ~8x on packed sites; 8-bit: 4 codes/word
        # -> just under 4x (the per-channel scales cost a few rows)
        assert 3.0 < rep8.packed_reduction < 4.0
        assert 6.0 < rep4.packed_reduction < 8.0
        assert rep4.packed_reduction > 1.7 * rep8.packed_reduction
        assert rep4.stored_bytes < rep8.stored_bytes < rep8.float32_bytes


# ---------------------------------------------------------------------------
# 5. plan-replay regression: packed evidence == unpacked evidence
# ---------------------------------------------------------------------------

class TestPlanReplayRegression:

    def test_serve_plan_evidence_identical(self, cfg, params):
        plan = _uniform_plan(cfg, params)
        prompt = jnp.asarray(
            np.random.default_rng(7).integers(0, cfg.vocab_size, (1, 4)),
            jnp.int32)
        mesh = single_device_mesh()
        ref = serve_lib.run_plan_execution(cfg, params, mesh, prompt,
                                           plan, 2)
        got = serve_lib.run_plan_execution(cfg, params, mesh, prompt,
                                           plan, 2, packed=True)
        np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                      np.asarray(ref["tokens"]))
        assert got["site_backends"] == ref["site_backends"]
        assert got["drift"] == ref["drift"]
        assert got["top1_agreement"] == ref["top1_agreement"]
        assert got["rel_rmse"] == ref["rel_rmse"]
        assert got["site_cycles"] == ref["site_cycles"]
        for cyc in got["site_cycles"].values():
            assert cyc["measured"] <= cyc["wc"] + 0.5

    def test_serving_engine_packed_streams_identical(self, cfg, params):
        trace = generate_trace(TrafficConfig(
            num_requests=4, arrival_rate=1.0, seed=3,
            prompt_short=(2, 4), prompt_long=(4, 6),
            output_short=(2, 3), output_long=(3, 5)))
        kw = dict(max_batch=2, page_size=4, max_seq_len=32,
                  backend="tubgemm", bits=4)
        ref = ServingEngine(cfg, params, **kw).run(trace, "continuous")
        eng = ServingEngine(cfg, params, packed=True, **kw)
        got = eng.run(trace, "continuous")
        assert got.request_tokens == ref.request_tokens
        assert got.energy_uj == ref.energy_uj  # pricing reads float leaves

    def test_serving_engine_packed_needs_scope(self, cfg, params):
        with pytest.raises(ValueError, match="packed=True needs"):
            ServingEngine(cfg, params, packed=True)


# ---------------------------------------------------------------------------
# 6. the stale-weight hazards + analysis rules
# ---------------------------------------------------------------------------

class TestHazards:

    def test_weight_matrix_refuses_packed_leaf(self, rng):
        leaf = packing.pack_quantized(
            jnp.asarray(rng.normal(0, 1, (8, 4)), jnp.float32), bits=4)
        site = planner_lib.GemmSite(name="blk/w", m=1, k=8, n_out=4,
                                    count=1, leaf=leaf)
        with pytest.raises(TypeError, match="already-packed"):
            site.weight_matrix()

    def test_measure_matrix_cycles_refuses_packed(self, rng):
        leaf = packing.pack_quantized(
            jnp.asarray(rng.normal(0, 1, (8, 4)), jnp.float32), bits=4)
        backend = backends.resolve("tubgemm", bits=4)
        with pytest.raises(TypeError, match="float weight"):
            backends.measure_matrix_cycles(backend, leaf, rows=1,
                                           unit_n=4, num_units=4)

    def test_plan_lint_packed_width_mismatch(self):
        plan = BackendPlan(sites=(
            SiteAssignment(pattern="layers/attn/wq", design="tubgemm",
                           bits=4),
            SiteAssignment(pattern="lm_head", design="bgemm", bits=8),))
        clean = plan_lint.lint_plan(
            plan, packed_bits={"layers/attn/wq": 4, "lm_head": 8})
        assert not [f for f in clean if f.rule == "packed-width-mismatch"]
        found = plan_lint.lint_plan(
            plan, packed_bits={"layers/attn/wq": 8, "unplanned/site": 2})
        hits = [f for f in found if f.rule == "packed-width-mismatch"]
        assert len(hits) == 1  # the unmatched site runs float: no conflict
        assert "repack" in hits[0].message

    def test_source_lint_packed_materialize_rule(self):
        bad = ("def packed_gemm(x, store):\n"
               "    w = store.dequantize()\n"
               "    return x @ w\n")
        found = source_lint.lint_source(
            bad, rel="src/repro/kernels/packed_gemm.py")
        assert [f.rule for f in found] == ["packed-materialize"]
        # elsewhere the same call is fine
        assert not source_lint.lint_source(
            bad, rel="src/repro/serving/energy.py")
        # and the shipped kernel module itself lints clean
        src = open(os.path.join(os.path.dirname(__file__), "..", "src",
                                "repro", "kernels", "packed_gemm.py")).read()
        assert not source_lint.lint_source(
            src, rel="src/repro/kernels/packed_gemm.py")


# ---------------------------------------------------------------------------
# 7. 2x2-grid subprocess parity (8 fake host devices)
# ---------------------------------------------------------------------------

MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro import backends, configs
from repro.backends.plan import BackendPlan, SiteAssignment
from repro.eval import planner
from repro.models import model as model_lib

cfg = configs.get_smoke_config("llama3-8b")
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
sites = planner.discover_sites(cfg, params)
flat = BackendPlan(sites=tuple(
    SiteAssignment(pattern=s.name, design="tubgemm", bits=4,
                   m=s.m, k=s.k, n_out=s.n_out, count=s.count)
    for s in sites))
gplan = backends.GridPlan(units_x=2, units_y=2, aggregate=flat, shards=())
packed = backends.pack_weights(cfg, params, gplan)
from repro.core import packing
leaf = next(l for l in jax.tree_util.tree_leaves(
    packed, is_leaf=packing.is_packed) if packing.is_packed(l))
assert leaf.grid_x == 2, leaf.grid_x  # per-shard word stores
tokens = jnp.zeros((2, 4), jnp.int32)
with backends.use_plan(gplan):
    ref, _ = model_lib.forward(params, cfg, tokens)
with backends.use_plan(gplan):
    got, _ = model_lib.forward(packed, cfg, tokens)
assert np.array_equal(np.asarray(got), np.asarray(ref))
print("PACKED_GRID_OK", len(sites))
"""


def test_packed_grid_multidevice():
    """On a 2x2 device mesh, executing from the per-shard packed store is
    bit-identical to the quantize-then-shard float path."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu",
           "JAX_DISABLE_MOST_OPTIMIZATIONS": "1",
           "JAX_COMPILATION_CACHE_DIR": os.path.abspath(".jax_cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    res = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert "PACKED_GRID_OK" in res.stdout, \
        f"missing PACKED_GRID_OK\n{res.stdout}\n{res.stderr}"
