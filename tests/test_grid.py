"""Sharded PE-array grid backends: topology/cost accounting, GridPlan
semantics, the shared measured-cycles helper, streamed site discovery, and
(in a pinned-device subprocess) multi-device bit-exactness + sharded plan
execution."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import backends, configs
from repro.backends.grid import (GRID_SCHEMA, GridBackend, GridPlan, as_grid,
                                 grid_matrix_cycles, load_plan, parse_grid,
                                 shard_site, shard_slices)
from repro.backends.plan import BackendPlan, SiteAssignment
from repro.core import accounting, ppa
from repro.eval import planner
from repro.models import common, model as model_lib

ALL_DESIGNS = ("ugemm", "tugemm", "tubgemm", "bgemm")
EXACT_DESIGNS = ("tugemm", "tubgemm", "bgemm")


@pytest.fixture(scope="module")
def llama_smoke():
    cfg = configs.get_smoke_config("llama3-8b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def llama_grid_plan(llama_smoke):
    cfg, params = llama_smoke
    return planner.build_grid_plan(cfg, params, batch=4, grid=(2, 2),
                                   unit_n=64, num_units=64)


def _codes(rng, shape, bits):
    v = 2 ** (bits - 1) - 1
    return jnp.asarray(rng.integers(-v, v + 1, shape), jnp.int8)


# ---------------------------------------------------------------------------
# Topology plumbing
# ---------------------------------------------------------------------------

class TestParseGrid:
    def test_accepts_tuple_list_and_strings(self):
        assert parse_grid((2, 4)) == (2, 4)
        assert parse_grid([2, 4]) == (2, 4)
        assert parse_grid("2,4") == (2, 4)
        assert parse_grid("2x4") == (2, 4)

    def test_rejects_bad_specs(self):
        with pytest.raises(ValueError):
            parse_grid("2,0")
        with pytest.raises(ValueError):
            parse_grid("2")
        with pytest.raises(ValueError):
            parse_grid((0, 1))

    def test_shard_site_format(self):
        assert shard_site((1, 2), "layers/attn/wq") == "1,2/layers/attn/wq"

    def test_shard_slices_cover_and_partition(self):
        slices = shard_slices(10, 7, 4, 2)
        cover = np.zeros((10, 7), np.int32)
        for rows, cols in slices.values():
            cover[rows, cols] += 1
        assert (cover == 1).all()  # exact partition of the real elements


class TestGridBackendBasics:
    def test_is_a_gemm_backend_with_inner_metadata(self):
        b = backends.resolve("tubgemm", bits=4)
        g = as_grid(b, 2, 2)
        assert isinstance(g, backends.GemmBackend)
        assert (g.name, g.bits, g.exact, g.pricing_design) == \
            (b.name, b.bits, b.exact, b.pricing_design)
        assert g.grid == (2, 2) and g.num_shards == 4
        assert g.inner() == b

    def test_regrid_is_reshape_not_nesting(self):
        g = as_grid(backends.resolve("bgemm", bits=8), 2, 2)
        g2 = as_grid(g, 4, 1)
        assert g2.grid == (4, 1) and g2.inner() == g.inner()

    def test_equality_distinguishes_grid_shapes(self):
        b = backends.resolve("tugemm", bits=4)
        assert as_grid(b, 2, 2) == as_grid(b, 2, 2)
        assert as_grid(b, 2, 2) != as_grid(b, 2, 1)
        assert as_grid(b, 1, 1) != b  # a grid is not its inner unit

    def test_resolve_passes_grid_backends_through(self):
        g = as_grid(backends.resolve("tubgemm", bits=4), 2, 2)
        assert backends.resolve(g) is g
        rewidthed = backends.resolve(g, bits=8)
        assert isinstance(rewidthed, GridBackend)
        assert rewidthed.bits == 8 and rewidthed.grid == (2, 2)

    def test_stream_refuses_with_guidance(self):
        g = as_grid(backends.resolve("tubgemm", bits=4), 2, 2)
        with pytest.raises(NotImplementedError, match="per shard"):
            g.stream(jnp.zeros((4, 4), jnp.int8), jnp.zeros((4, 4), jnp.int8))

    @pytest.mark.parametrize("design", ALL_DESIGNS)
    def test_degenerate_grid_execute_matches_inner(self, rng, design):
        """(1,1) runs the real shard_map path on the single CPU device."""
        b = backends.resolve(design, bits=4)
        g = as_grid(b, 1, 1)
        a = _codes(rng, (6, 24), 4)
        w = _codes(rng, (24, 10), 4)
        np.testing.assert_array_equal(np.asarray(g.execute(a, w)),
                                      np.asarray(b.execute(a, w)))

    def test_batched_execute_shapes(self, rng):
        g = as_grid(backends.resolve("bgemm", bits=4), 1, 1)
        a = _codes(rng, (3, 5, 8), 4)
        w_shared = _codes(rng, (8, 6), 4)
        w_each = _codes(rng, (3, 8, 6), 4)
        assert g.execute(a, w_shared).shape == (3, 5, 6)
        assert g.execute(a, w_each).shape == (3, 5, 6)


# ---------------------------------------------------------------------------
# Cycle + cost accounting
# ---------------------------------------------------------------------------

class TestGridCycles:
    def test_hop_term_and_shard_common_dim(self):
        g = as_grid(backends.resolve("tubgemm", bits=4), 4, 2)
        assert g.hop_cycles() == ppa.HOP_CYCLES * (3 + 1)
        assert g.shard_common_dim(64) == 16
        assert g.shard_common_dim(10) == 3  # ceil split
        inner = g.inner()
        assert g.cycles(64) == inner.cycles(16) + g.hop_cycles()

    def test_wc_cycles_decrease_with_k_partitions_for_large_k(self):
        b = backends.resolve("tubgemm", bits=4)
        k = 4096
        chain = [as_grid(b, x, 1).cycles(k) for x in (1, 2, 4, 8)]
        assert chain == sorted(chain, reverse=True)
        assert chain[-1] < chain[0]

    @pytest.mark.parametrize("design", ALL_DESIGNS)
    def test_operand_dyn_cycles_within_bounds(self, rng, design):
        g = as_grid(backends.resolve(design, bits=4), 2, 2)
        q = _codes(rng, (32, 12), 4)
        measured = g.dyn_cycles(operand=q)
        wc = g.cycles(32)
        floor = g.dyn_cycles(32, bit_sparsity=0.999)
        assert floor <= measured <= wc

    def test_operand_and_sparsity_are_mutually_exclusive(self):
        g = as_grid(backends.resolve("tubgemm", bits=4), 2, 1)
        with pytest.raises(ValueError, match="not both"):
            g.dyn_cycles(operand=jnp.zeros((4,)), bit_sparsity=0.5)
        with pytest.raises(ValueError, match="common_dim"):
            g.dyn_cycles(bit_sparsity=0.5)

    def test_sparsity_only_helps_sparsity_aware_designs(self):
        gt = as_grid(backends.resolve("tubgemm", bits=4), 2, 2)
        gb = as_grid(backends.resolve("bgemm", bits=4), 2, 2)
        assert gt.dyn_cycles(64, bit_sparsity=0.5) < gt.cycles(64)
        assert gb.dyn_cycles(64, bit_sparsity=0.5) == gb.cycles(64)


class TestGridCost:
    def _calls(self):
        return [accounting.GemmCall("a", 4, 64, 192, 0.3, 2),
                accounting.GemmCall("b", 4, 192, 64, 0.2, 2)]

    def test_grid_cost_is_a_model_cost_with_grid_fields(self):
        cost = accounting.price_workload(self._calls(), design="tubgemm",
                                         bits=4, unit_n=64, num_units=64,
                                         grid=(2, 2))
        assert isinstance(cost, accounting.ModelCost)
        assert isinstance(cost, accounting.GridCost)
        assert cost.grid == (2, 2)
        assert cost.hop_energy_uj > 0
        assert 0 < cost.hop_energy_share < 1
        assert cost.utilization == 1.0

    def test_grid_backend_prices_itself_through_the_grid_branch(self):
        g = as_grid(backends.resolve("tubgemm", bits=4), 2, 2)
        cost = g.price(self._calls(), unit_n=64, num_units=64)
        explicit = accounting.price_workload(
            self._calls(), design="tubgemm", bits=4, unit_n=64,
            num_units=64, grid=(2, 2))
        assert cost == explicit

    @pytest.mark.parametrize("design", ALL_DESIGNS)
    def test_energy_monotone_in_grid_refinement(self, design):
        chain = [(1, 1), (1, 2), (2, 2), (2, 4), (4, 4)]
        costs = [accounting.price_workload(self._calls(), design=design,
                                           bits=4, unit_n=64, num_units=64,
                                           grid=g) for g in chain]
        energies = [c.dyn_energy_uj for c in costs]
        assert energies == sorted(energies)

    def test_padding_shows_up_as_utilization_below_one(self):
        calls = [accounting.GemmCall("odd", 4, 65, 33, 0.0, 1)]
        cost = accounting.price_workload(calls, design="bgemm", bits=4,
                                         unit_n=64, num_units=64,
                                         grid=(4, 4))
        assert cost.utilization < 1.0

    def test_trivial_grid_matches_flat_pricing_plus_type(self):
        flat = accounting.price_workload(self._calls(), design="tubgemm",
                                         bits=4, unit_n=64, num_units=64)
        g11 = accounting.price_workload(self._calls(), design="tubgemm",
                                        bits=4, unit_n=64, num_units=64,
                                        grid=(1, 1))
        assert g11.hop_energy_uj == 0.0
        assert g11.dyn_energy_uj == pytest.approx(flat.dyn_energy_uj)
        assert g11.wc_latency_us == pytest.approx(flat.wc_latency_us)


# ---------------------------------------------------------------------------
# Shared measured-cycles helper (the deduplicated serve/planner contract)
# ---------------------------------------------------------------------------

class TestMeasureMatrixCycles:
    @pytest.mark.parametrize("design", EXACT_DESIGNS)
    def test_bounds_hold_per_design(self, rng, design):
        b = backends.resolve(design, bits=4)
        w = rng.normal(0, 1, (48, 24)).astype(np.float32)
        cyc = backends.measure_matrix_cycles(b, w, rows=4, unit_n=16,
                                             num_units=4)
        assert cyc["dyn_floor"] - 1e-6 <= cyc["measured"] <= cyc["wc"] + 1e-6
        # tiles(4, 24) on 16x16 units = 2; ceil(2 / 4 units) = 1 wave
        assert cyc["wc"] == b.cycles(48)

    def test_non_sparsity_aware_designs_report_all_equal(self, rng):
        b = backends.resolve("bgemm", bits=4)
        w = rng.normal(0, 1, (32, 16)).astype(np.float32)
        cyc = backends.measure_matrix_cycles(b, w, rows=2, unit_n=16,
                                             num_units=4)
        assert cyc["measured"] == cyc["dyn"] == cyc["dyn_floor"] == cyc["wc"]

    def test_grid_backend_waves_use_shard_output_share(self, rng):
        """A grid's per-tile cycles already cover the ceil-split K; the wave
        count must come from a shard's output-column share, not the full
        matrix (shards run their waves in parallel)."""
        w = rng.normal(0, 1, (64, 64)).astype(np.float32)
        flat = backends.resolve("bgemm", bits=4)
        g = as_grid(flat, 1, 4)
        # unit_n=16, num_units=1: flat tiles(4,64)=4 waves; per shard
        # tiles(4,16)=1 wave.  bgemm wc = k cycles per tile (+0 grid hops
        # on the k axis; 3 column hops).
        flat_cyc = backends.measure_matrix_cycles(flat, w, rows=4,
                                                  unit_n=16, num_units=1)
        grid_cyc = backends.measure_matrix_cycles(g, w, rows=4,
                                                  unit_n=16, num_units=1)
        assert flat_cyc["wc"] == 64 * 4
        assert grid_cyc["wc"] == (64 + g.hop_cycles()) * 1

    def test_supplied_stats_skip_reprofiling(self, rng):
        b = backends.resolve("tubgemm", bits=4)
        w = rng.normal(0, 1, (32, 16)).astype(np.float32)
        cyc = backends.measure_matrix_cycles(b, w, rows=2, unit_n=16,
                                             num_units=4, bit_blockmax=0.5,
                                             bit_elem=0.75)
        assert cyc["dyn"] == pytest.approx(b.cycles(32) * 0.5)
        assert cyc["dyn_floor"] == pytest.approx(b.cycles(32) * 0.25)

    def test_serve_totals_are_sums_of_the_shared_helper(self, llama_smoke):
        """Dedup contract, serve side: ``measure_decode_cycles`` is exactly
        the shared helper summed over serve's weight walk."""
        from repro.launch import serve as serve_lib
        cfg, params = llama_smoke
        backend = backends.resolve("tubgemm", bits=4)
        want = {"measured": 0.0, "dyn": 0.0, "dyn_floor": 0.0, "wc": 0.0}
        for _name, w in serve_lib._iter_weight_matrices(cfg, params):
            cyc = backends.measure_matrix_cycles(backend, w, rows=4,
                                                 unit_n=64, num_units=64)
            for key in want:
                want[key] += cyc[key]
        got = serve_lib.measure_decode_cycles(cfg, params, backend, batch=4,
                                              unit_n=64, num_units=64)
        for key in want:
            assert got[key] == pytest.approx(want[key])

    def test_planner_site_cycles_are_sums_of_the_shared_helper(
            self, llama_smoke):
        """Dedup contract, planner side: ``measure_site_cycles`` is exactly
        the shared helper summed over the site's physical weight copies."""
        cfg, params = llama_smoke
        sites = {s.name: s for s in planner.discover_sites(cfg, params,
                                                           batch=4)}
        site = sites["layers/mlp/w_up"]
        entry = SiteAssignment(pattern=site.name, design="tubgemm", bits=4,
                               bit_blockmax=0.3, bit_elem=0.6)
        backend = entry.backend()
        w3 = site.weight_matrix().reshape(-1, site.k, site.n_out)
        want = {"measured": 0.0, "dyn": 0.0, "dyn_floor": 0.0, "wc": 0.0}
        for i in range(w3.shape[0]):
            cyc = backends.measure_matrix_cycles(
                backend, w3[i], rows=site.m, unit_n=64, num_units=64,
                bit_blockmax=0.3, bit_elem=0.6)
            for key in want:
                want[key] += cyc[key]
        got = planner.measure_site_cycles(site, entry, unit_n=64,
                                          num_units=64)
        for key in want:
            assert got[key] == pytest.approx(want[key])

    def test_grid_matrix_cycles_per_shard_bounds(self, rng):
        g = as_grid(backends.resolve("tubgemm", bits=4), 2, 2)
        w = rng.normal(0, 1, (64, 32)).astype(np.float32)
        per_shard = grid_matrix_cycles(g, w, rows=4, unit_n=16, num_units=4)
        assert set(per_shard) == {"0,0", "0,1", "1,0", "1,1"}
        hops = g.hop_cycles()
        for cyc in per_shard.values():
            assert cyc["dyn_floor"] - 1e-6 <= cyc["measured"] \
                <= cyc["wc"] + 1e-6
            assert cyc["wc"] >= hops  # the hop term rides every bound


# ---------------------------------------------------------------------------
# Streamed site discovery (memory-hazard fix)
# ---------------------------------------------------------------------------

class TestStreamedDiscovery:
    def test_sites_hold_leaves_by_reference(self, llama_smoke):
        cfg, params = llama_smoke
        sites = {s.name: s for s in planner.discover_sites(cfg, params,
                                                           batch=2)}
        flat = {"/".join(str(getattr(p, "key", p)) for p in path): leaf
                for path, leaf in
                jax.tree_util.tree_flatten_with_path(params)[0]}
        wq = sites["layers/attn/wq"]
        assert wq.leaf is flat["layers/attn/wq"]  # zero-copy discovery

    def test_weight_matrix_materializes_on_demand(self, llama_smoke):
        cfg, params = llama_smoke
        sites = {s.name: s for s in planner.discover_sites(cfg, params,
                                                           batch=2)}
        wq = sites["layers/attn/wq"]
        w = wq.weight_matrix()
        assert isinstance(w, np.ndarray) and w.dtype == np.float32
        assert w.shape == (wq.count * wq.k, wq.n_out)
        # the back-compat property keeps the old surface
        assert wq.weight.shape == w.shape


# ---------------------------------------------------------------------------
# GridPlan semantics
# ---------------------------------------------------------------------------

class TestGridPlan:
    def test_per_shard_planned_beats_every_shard_uniform(self,
                                                         llama_grid_plan):
        meta = llama_grid_plan.metadata()
        for key, verdict in meta["totals"]["per_shard"].items():
            planned = verdict["planned"]["dyn_energy_uj"]
            for name, tot in verdict["uniform"].items():
                assert planned <= tot["dyn_energy_uj"] * (1 + 1e-9), \
                    f"shard {key} lost to uniform {name}"

    def test_aggregate_planned_beats_every_uniform_grid(self,
                                                        llama_grid_plan):
        agg = llama_grid_plan.metadata()["totals"]["aggregate"]
        for name, tot in agg["uniform"].items():
            assert agg["planned"]["dyn_energy_uj"] \
                <= tot["dyn_energy_uj"] * (1 + 1e-9)
            assert agg["planned_heterogeneous"]["dyn_energy_uj"] \
                <= tot["dyn_energy_uj"] * (1 + 1e-9)

    def test_heterogeneous_planned_no_worse_than_executed(self,
                                                          llama_grid_plan):
        agg = llama_grid_plan.metadata()["totals"]["aggregate"]
        assert agg["planned_heterogeneous"]["dyn_energy_uj"] \
            <= agg["planned"]["dyn_energy_uj"] * (1 + 1e-9)

    def test_shipped_smoke_grid_plan_is_mixed(self, llama_grid_plan):
        assert len(llama_grid_plan.shard_distinct_backends()) >= 2

    def test_round_trip_is_byte_stable(self, llama_grid_plan):
        text = llama_grid_plan.to_json()
        again = GridPlan.from_json(text)
        assert again.to_json() == text
        assert again.grid == llama_grid_plan.grid

    def test_load_plan_sniffs_both_schemas(self, tmp_path, llama_grid_plan):
        gp = tmp_path / "grid.json"
        llama_grid_plan.save(gp)
        assert isinstance(load_plan(gp), GridPlan)
        flat = tmp_path / "flat.json"
        llama_grid_plan.aggregate.save(flat)
        assert isinstance(load_plan(flat), BackendPlan)
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"schema": "nope"}))
        with pytest.raises(ValueError, match="unknown plan schema"):
            load_plan(bad)

    def test_plain_site_names_resolve_grid_wrapped(self, llama_grid_plan):
        b = llama_grid_plan.backend_for("layers/attn/wq")
        assert isinstance(b, GridBackend)
        assert b.grid == llama_grid_plan.grid

    def test_shard_local_site_names_resolve_single_node(self,
                                                        llama_grid_plan):
        for key, shard_plan in llama_grid_plan.shards:
            entry = shard_plan.assignment_for("layers/attn/wq")
            gx, gy = (int(p) for p in key.split(","))
            b = llama_grid_plan.backend_for(
                shard_site((gx, gy), "layers/attn/wq"))
            assert not isinstance(b, GridBackend)
            assert (b.name, b.bits) == (entry.design, entry.bits)

    def test_unknown_site_resolves_none(self, llama_grid_plan):
        assert llama_grid_plan.backend_for("not/a/site") is None
        assert llama_grid_plan.backend_for("9,9/layers/attn/wq") is None

    def test_shard_qualified_miss_never_falls_back_to_aggregate(self):
        """A shard-local name must not leak into the aggregate's globs."""
        flat = BackendPlan(sites=(SiteAssignment(pattern="*",
                                                 design="tubgemm", bits=4),))
        gplan = GridPlan(units_x=2, units_y=2, aggregate=flat, shards=())
        assert gplan.backend_for("5,5/layers/attn/wq") is None
        assert gplan.backend_for("0,0/layers/attn/wq") is None  # no shard plan
        assert isinstance(gplan.backend_for("layers/attn/wq"), GridBackend)

    def test_planner_wc_totals_match_the_grid_pricer(self):
        """Aggregate candidate costs must agree with GridDLAModel (energy
        summed over ALL shards incl. pure-padding ones, latency = slowest
        shard), pinned via the stat-independent worst case on a
        non-divisible site."""
        leaf = np.random.default_rng(0).normal(0, 1, (5, 12)) \
            .astype(np.float32)
        site = planner.GemmSite(name="odd", m=4, k=5, n_out=12, count=1,
                                leaf=leaf)
        cfg = configs.get_smoke_config("llama3-8b")
        gplan = planner.build_grid_plan(cfg, None, grid=(4, 2),
                                        bits_candidates=(4,),
                                        designs=("tubgemm",),
                                        unit_n=16, num_units=4,
                                        sites=[site])
        gdla = ppa.GridDLAModel(design="tubgemm", bits=4, n=16, num_units=4,
                                units_x=4, units_y=2)
        want_e = gdla.matmul_energy_nj(4, 5, 12, 0.0) * 1e-3
        want_l = gdla.matmul_latency_ns(4, 5, 12, 0.0) * 1e-3
        agg = gplan.metadata()["totals"]["aggregate"]
        got = agg["uniform"]["tubgemm@4"]
        assert got["wc_energy_uj"] == pytest.approx(want_e)
        assert got["wc_latency_us"] == pytest.approx(want_l)

    def test_use_plan_rejects_conflicting_grid(self, llama_grid_plan):
        with pytest.raises(ValueError, match="conflicts"):
            with backends.use_plan(llama_grid_plan, grid=(4, 1)):
                pass

    def test_markdown_renders(self, llama_grid_plan):
        md = planner.grid_plan_to_markdown(llama_grid_plan)
        assert "Per-shard verdicts" in md
        assert "uniform" in md.lower()


class TestGridPlanExecution:
    """Degenerate (1,1) grids exercise the sharded dense path on the single
    tier-1 CPU device; the multi-device path runs in the subprocess test."""

    def _dense_site(self, w, x, plan_like):
        with backends.use_plan(plan_like) as execution:
            with backends.site_scope("blk"):
                out = common.dense(w, x, name="w")
        return out, execution

    def test_grid_plan_execution_bit_exact_vs_flat_backend(self, rng):
        w = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (4, 16)), jnp.float32)
        flat = BackendPlan(sites=(SiteAssignment(pattern="blk/w",
                                                 design="tubgemm", bits=4),))
        gplan = GridPlan(units_x=1, units_y=1, aggregate=flat, shards=())
        out_grid, execution = self._dense_site(w, x, gplan)
        with backends.use_backend("tubgemm", bits=4):
            with backends.site_scope("blk"):
                out_flat = common.dense(w, x, name="w")
        np.testing.assert_array_equal(np.asarray(out_grid),
                                      np.asarray(out_flat))
        assert [c.site for c in execution.calls] == ["blk/w"]
        assert execution.calls[0].backend == "tubgemm"

    def test_use_plan_grid_kwarg_wraps_flat_plans(self, rng):
        w = jnp.asarray(rng.normal(0, 1, (12, 6)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (3, 12)), jnp.float32)
        plan = BackendPlan(sites=(SiteAssignment(pattern="*", design="bgemm",
                                                 bits=8),))
        with backends.use_plan(plan, grid="1,1") as execution:
            common.dense(w, x, name="w")
        backend = execution.backend_for("w")
        assert isinstance(backend, GridBackend)
        assert backend.grid == (1, 1)


# ---------------------------------------------------------------------------
# Multi-device: bit-exactness + sharded plan replay (pinned subprocess)
# ---------------------------------------------------------------------------

MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro import backends, configs
from repro.backends.plan import BackendPlan, SiteAssignment
from repro.eval import planner
from repro.models import common, model as model_lib
from jax.sharding import PartitionSpec as P

rng = np.random.default_rng(0)

# ---- 1. grid execute bit-exact vs the single-unit backend ------------------
for bits in (2, 4, 8):
    v = 2 ** (bits - 1) - 1
    a = jnp.asarray(rng.integers(-v, v + 1, (6, 24)), jnp.int8)
    w = jnp.asarray(rng.integers(-v, v + 1, (24, 20)), jnp.int8)
    for design in ("ugemm", "tugemm", "tubgemm", "bgemm"):
        b = backends.resolve(design, bits=bits)
        ref = np.asarray(b.execute(a, w))
        for grid in ((2, 2), (4, 2), (3, 2)):
            got = np.asarray(backends.as_grid(b, *grid).execute(a, w))
            assert np.array_equal(got, ref), (design, bits, grid)
print("GRID_BITEXACT_OK")

# ---- 2. site lookup resolves identically on every shard --------------------
# A (2,2) grid plan executes the model SPMD: the traced dense sites must be
# exactly the flat plan's sites, and an exact design's logits bit-identical
# to the unsharded use_backend run.
cfg = configs.get_smoke_config("llama3-8b")
params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
tokens = jnp.zeros((2, 4), jnp.int32)
flat = BackendPlan(sites=(SiteAssignment(pattern="*", design="tubgemm",
                                         bits=4),))
gplan = backends.GridPlan(units_x=2, units_y=2, aggregate=flat, shards=())
with backends.use_plan(gplan) as grid_exec:
    logits_grid, _ = model_lib.forward(params, cfg, tokens)
with backends.use_backend("tubgemm", bits=4) as flat_exec:
    logits_flat, _ = model_lib.forward(params, cfg, tokens)
grid_sites = sorted(c.site for c in grid_exec.calls)
flat_sites = sorted(c.site for c in flat_exec.calls)
assert grid_sites == flat_sites, (grid_sites, flat_sites)
assert all(isinstance(grid_exec.backend_for(s), backends.GridBackend)
           for s in grid_sites)
assert np.array_equal(np.asarray(logits_grid), np.asarray(logits_flat))
print("GRID_MODEL_BITEXACT_OK", len(grid_sites))

# ---- 3. per-shard heterogeneous plan: derive + grid-execute ----------------
gp = planner.build_grid_plan(cfg, params, batch=2, grid=(2, 2), unit_n=64,
                             num_units=64)
with backends.use_plan(gp) as execution:
    logits_plan, _ = model_lib.forward(params, cfg, tokens)
assert len(execution.calls) == len(gp.aggregate.sites)
tags = {c.site: (c.backend, c.bits) for c in execution.calls}
for entry in gp.aggregate.sites:
    assert tags[entry.pattern] == (entry.design, entry.bits)
print("GRID_PLAN_REPLAY_OK", len(gp.heterogeneous_sites()))

# ---- 4. dense inside an explicit shard_map sees the same site --------------
# (the models/common.dense site-lookup contract under shard_map: trace-time
# thread-local state is shared by every shard of the single SPMD trace)
from repro.launch.mesh import make_grid_mesh
mesh = make_grid_mesh(2, 2)
w2 = jnp.asarray(rng.normal(0, 1, (8, 4)), jnp.float32)
x2 = jnp.asarray(rng.normal(0, 1, (4, 8)), jnp.float32)
with backends.use_backend("bgemm", bits=8) as execution:
    def body(xs):
        with backends.site_scope("inner"):
            return common.dense(w2, xs, name="w")
    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
    out_sharded = fn(x2)
assert [c.site for c in execution.calls] == ["inner/w"]
with backends.use_backend("bgemm", bits=8):
    with backends.site_scope("inner"):
        out_ref = common.dense(w2, x2, name="w")
assert np.array_equal(np.asarray(out_sharded), np.asarray(out_ref))
print("DENSE_UNDER_SHARD_MAP_OK")
"""


def test_grid_multidevice():
    """The acceptance claim: on a >= 4-device host mesh, GridBackend.execute
    is bit-exact vs the single-unit backend for every simulated design at
    bits {2, 4, 8}, per-shard plans replay SPMD, and dense's site lookup
    resolves identically on every shard."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu",
           "JAX_DISABLE_MOST_OPTIMIZATIONS": "1",
           "JAX_COMPILATION_CACHE_DIR": os.path.abspath(".jax_cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    res = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    out = res.stdout
    for marker in ("GRID_BITEXACT_OK", "GRID_MODEL_BITEXACT_OK",
                   "GRID_PLAN_REPLAY_OK", "DENSE_UNDER_SHARD_MAP_OK"):
        assert marker in out, f"missing {marker}\n{out}\n{res.stderr}"
