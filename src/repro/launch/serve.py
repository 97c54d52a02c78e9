"""Batched serving driver: prefill + decode with unary-DLA energy accounting.

This is where the paper's technique meets the serving stack:

* **pricing** (always on): every quantized GEMM in the model is priced on a
  chosen unary/binary PE-array backend (--gemm-backend, --bits) using the
  *measured* block-max bit sparsity of the actual weights (Eq. 1), giving
  per-token energy/latency for the whole model alongside the generated tokens.
* **execution** (--execute-backend): prefill and decode actually run every
  quantized dense layer through a typed ``repro.backends`` engine — int
  tiles contracted on the selected unary design (or its Pallas kernel
  mirror), dequantized back to the activation dtype — and the driver reports
  the int GEMMs' bit-exactness vs the binary oracle, the output drift vs the
  float model, and the measured cycle totals against the priced dyn/wc
  bounds.
* **planning** (``serve plan``): derive a per-layer mixed-precision backend
  plan for the served config (``repro.eval.planner``), save it to
  ``--plan-out``, and report predicted vs uniform-backend energy plus the
  measured decode-cycle totals per site.
* **plan replay** (--backend-plan FILE): execute prefill+decode with every
  dense site contracted on the backend its plan entry names, with the same
  bit-exactness / drift / cycle-bounds evidence as --execute-backend, per
  site.
* **grid serving** (--grid X,Y): everything above on a tensor-parallel
  PE-array grid.  ``serve plan --grid X,Y`` derives a per-shard
  heterogeneous ``GridPlan`` (each shard's weight slice has its own
  sparsity profile); execution modes shard every dense contraction under
  ``shard_map`` on an X×Y device mesh (``launch.mesh.make_grid_mesh``) with
  the k-dim partial sums psum-reduced, report bit-exactness vs the
  *unsharded* binary oracle, and check measured cycles within the
  [Eq. 1 floor, wc] bounds per shard.

    PYTHONPATH=src python -m repro.launch.serve plan --arch llama3-8b \
        --smoke --unit-n 64 --plan-out reports/plan.json
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --backend-plan reports/plan.json --tokens 8
    # sharded: derive + replay a 2x2 grid plan on 4+ (fake) host devices
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve plan --arch llama3-8b --smoke \
        --unit-n 64 --grid 2,2 --plan-out reports/grid_plan.json
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch llama3-8b --smoke \
        --backend-plan reports/grid_plan.json --grid 2,2 --tokens 8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import backends as backends_lib
from repro import configs
from repro.core import accounting, packing, ppa, sparsity
from repro.core import gemm_sims as gemm_sims_lib
from repro.core.quantization import quantize
from repro.eval import planner as planner_lib
from repro.eval import sweetspot as sweetspot_lib
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_grid_mesh, single_device_mesh
from repro.models import model as model_lib
from repro.launch.compile_cache import enable_compilation_cache
from repro.serving import ServingEngine, TrafficConfig, generate_trace
from repro.serving import energy as serving_energy
from repro.serving.logit_check import check_decode_logits


def _iter_weight_matrices(cfg, params):
    """Yield ``(name, (k, n_out) float32 weight)`` for every priced matmul.

    The single walk the pricing workload, the measured-cycle report AND the
    serving engine's energy-per-token model are built from (the canonical
    implementation lives in ``repro.serving.energy``), so they all see
    identical matrices.
    """
    return serving_energy.iter_weight_matrices(cfg, params)


def build_workload(cfg, params, batch: int, ctx_len: int, bits: int):
    """GemmCalls for ONE decode step, with measured per-matrix sparsity."""
    rec = accounting.GemmWorkloadRecorder()
    stats = {}
    for name, w in _iter_weight_matrices(cfg, params):
        st = sparsity.profile_tensor(jnp.asarray(w), bits=bits)
        stats[name] = st
        k, n_out = w.shape
        rec.record(name, m=batch, k=k, n_out=n_out,
                   bit_sparsity=st.bit_blockmax, count=1)
    return rec, stats


def validate_backend_numerics(params, design, bits: int | None = None,
                              n_tiles: int = 8, tile: int = 16,
                              oracle: str = "bgemm") -> float:
    """Spot-check the selected GEMM backend on tiles of the real weights.

    Quantizes ``n_tiles`` (tile x tile) slices of actual model weights,
    stacks them on a batch axis, and pushes the whole stack through
    ``GemmBackend.execute`` in one batched call against the ``oracle``
    design (binary by default).  ``design`` is a backend name or
    ``repro.backends.GemmBackend`` (``bits`` then defaults to the backend's
    own width).  Exact designs (tu/tub/b and the Pallas mirrors) must come
    back bit-identical — returns 0.0 — while uGEMM reports its stochastic
    relative RMSE.  Rate-coded stochastic backends are judged with
    ``oracle="ugemm"`` — the exact uGEMM value their bitstreams converge to
    at L=2^bits — so the number isolates the *stream-length* error.
    """
    backend = backends_lib.resolve(design, bits=bits)
    oracle = backends_lib.resolve(oracle, bits=backend.bits)
    # Packed leaves dequantize for tiling — the spot-check wants float
    # matrices to quantize fresh at the backend's width.
    leaves = [l.dequantize() if packing.is_packed(l) else l
              for l in jax.tree_util.tree_leaves(
                  params, is_leaf=packing.is_packed)]
    leaves = [l for l in leaves
              if hasattr(l, "ndim") and l.ndim >= 2 and l.size >= 2 * tile * tile]
    if not leaves:
        return 0.0
    tiles = []
    for i in range(2 * n_tiles):
        flat = np.asarray(leaves[i % len(leaves)], np.float32).reshape(-1)
        off = (i // len(leaves)) * tile * tile
        chunk = flat[off:off + tile * tile]
        if chunk.size < tile * tile:
            chunk = flat[:tile * tile]
        q = quantize(jnp.asarray(chunk.reshape(tile, tile)), bits=backend.bits,
                     per_channel=False)
        tiles.append(q.values.astype(jnp.int8))
    a = jnp.stack(tiles[:n_tiles])
    b = jnp.stack(tiles[n_tiles:])
    return gemm_sims_lib.rel_rmse(backend.execute(a, b), oracle.execute(a, b))


def _oracle_for(backend) -> str:
    """The oracle design a backend's numerics are judged against.

    Rate-coded stochastic backends carry a ``stream_len`` and converge to
    the exact uGEMM value, so that is their reference; everything else is
    checked against the binary int32 oracle.
    """
    return "ugemm" if getattr(backend, "stream_len", None) else "bgemm"


def measure_decode_cycles(cfg, params, backend, *, batch: int, unit_n: int,
                          num_units: int, stats=None) -> dict[str, float]:
    """Per-decode-token cycle totals for the model on one backend.

    Sums the shared measured-cycles contract
    (``repro.backends.measure_matrix_cycles`` — the same helper behind the
    planner's ``measure_site_cycles``) over every priced weight matrix.
    Four numbers per the DLA tiling ``core.ppa.DLAModel`` uses (per-tile
    cycles x ceil(tiles / num_units) waves, common dim = k):

    * ``wc`` — worst case, ``backend.cycles(k)`` per tile;
    * ``dyn_floor`` — Eq. 1 with *element-level* bit sparsity: every lane
      terminating at its own magnitude, an optimistic lower bound the shared
      slot schedule cannot beat;
    * ``measured`` — operand-driven: ``backend.dyn_cycles(operand=...)`` on
      the same **per-channel** quantized codes ``models/common.dense``
      contracts under ``use_backend`` — the cycles the early-terminating
      counters really take, with each outer-product step gated by the
      largest magnitude in flight;
    * ``dyn`` — the priced Eq. 1 estimate (worst case scaled by the
      block-max bit sparsity the cost tables use): gating at PE-block
      granularity.  Comparable to ``measured`` but not a bound on it — the
      statistic profiles a per-tensor grid while execution contracts
      per-channel codes.

    For sparsity-aware designs ``dyn_floor <= measured <= wc`` (wc caps
    every step); designs without early termination report all four equal.
    The serve driver checks ``dyn_floor <= measured <= wc``.

    ``stats`` — optional ``{name: SparsityStats}`` at ``backend.bits`` (from
    ``build_workload``) to skip re-profiling every weight matrix.
    """
    totals = {"wc": 0.0, "dyn": 0.0, "dyn_floor": 0.0, "measured": 0.0}
    for name, w in _iter_weight_matrices(cfg, params):
        st = (stats or {}).get(name)
        cyc = backends_lib.measure_matrix_cycles(
            backend, w, rows=batch, unit_n=unit_n, num_units=num_units,
            bit_blockmax=None if st is None else st.bit_blockmax,
            bit_elem=None if st is None else st.bit_elem)
        for key in totals:
            totals[key] += cyc[key]
    return totals


def generate(cfg, params, mesh, prompt, max_new: int, temperature: float = 0.0):
    """Greedy/temperature decoding with the jitted prefill/decode steps."""
    b, s = prompt.shape
    max_len = s + max_new
    prefill_step = steps_lib.make_prefill_step(cfg, mesh, params_like=params)
    decode_step = steps_lib.make_decode_step(cfg, mesh, params_like=params)
    with jax.set_mesh(mesh):
        caches = model_lib.init_caches(cfg, b, max_len, dtype=jnp.float32)
        logits, caches = prefill_step(params, {"tokens": prompt}, caches)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out = [tok]
        key = jax.random.PRNGKey(0)
        for i in range(max_new - 1):
            logits, caches = decode_step(params, tok, caches,
                                         jnp.int32(s + i))
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(
                    sub, logits[:, -1] / temperature)[:, None].astype(jnp.int32)
            else:
                tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
            out.append(tok)
    return jnp.concatenate(out, axis=1)


def prefill_logits(cfg, params, mesh, prompt):
    """Full prefill logits via a freshly traced step (so an active
    ``use_backend`` scope is honored — jitted steps bind the backend at
    trace time)."""
    prefill_step = steps_lib.make_prefill_step(cfg, mesh, params_like=params)
    with jax.set_mesh(mesh):
        caches = model_lib.init_caches(cfg, prompt.shape[0],
                                       prompt.shape[1] + 1, dtype=jnp.float32)
        logits, _ = prefill_step(params, {"tokens": prompt}, caches)
    return logits


def run_backend_execution(cfg, params, mesh, prompt, backend, max_new: int,
                          *, unit_n: int, num_units: int,
                          ref_logits=None, stats=None,
                          packed: bool = False) -> dict:
    """Execute prefill+decode on ``backend`` and collect the evidence.

    Returns a dict: generated ``tokens``, number of distinct GEMM ``sites``
    contracted on the backend, int-GEMM ``rel_rmse`` vs the binary oracle,
    prefill-logits ``drift`` + ``top1_agreement`` vs the float model, wall
    time, and the measured/dyn/wc ``cycles`` totals per decode token.
    ``stats`` — optional pre-profiled sparsity stats at the backend's
    bit-width, forwarded to :func:`measure_decode_cycles`.  ``packed``
    freezes every GEMM site's weight bit-packed at the backend's width and
    executes from the packed store; the float ``params`` keep feeding the
    reference/measurement paths, so the evidence is comparable — and the
    execution is bit-identical — to the unpacked run.
    """
    backend = backends_lib.resolve(backend)
    exec_params = (backends_lib.pack_weights(cfg, params, bits=backend.bits)
                   if packed else params)
    if ref_logits is None:
        ref_logits = prefill_logits(cfg, params, mesh, prompt)
    t0 = time.time()
    with backends_lib.use_backend(backend) as execution:
        tokens = generate(cfg, exec_params, mesh, prompt, max_new)
        exec_logits = prefill_logits(cfg, exec_params, mesh, prompt)
    wall = time.time() - t0
    if not execution.calls:
        raise RuntimeError(
            "backend execution recorded no GEMM sites — the model traced "
            "outside the use_backend scope?")
    ref = np.asarray(ref_logits, np.float32)
    got = np.asarray(exec_logits, np.float32)
    agree = float(np.mean(np.argmax(got, -1) == np.argmax(ref, -1)))
    oracle = _oracle_for(backend)
    return {
        "backend": backend,
        "tokens": tokens,
        "sites": len(execution.calls),
        "wall_s": wall,
        "oracle": oracle,
        "rel_rmse": validate_backend_numerics(params, backend, oracle=oracle),
        "drift": gemm_sims_lib.rel_rmse(got, ref),
        "top1_agreement": agree,
        "cycles": measure_decode_cycles(cfg, params, backend,
                                        batch=prompt.shape[0], unit_n=unit_n,
                                        num_units=num_units, stats=stats),
    }


def run_plan_execution(cfg, params, mesh, prompt, plan, max_new: int,
                       *, ref_logits=None, packed: bool = False) -> dict:
    """Execute prefill+decode under ``use_plan`` and collect the evidence.

    Like :func:`run_backend_execution` but per-site: every dense site
    contracts on the backend its plan entry names (unmatched sites stay
    float).  ``plan`` may be a ``BackendPlan`` or a ``GridPlan`` — a grid
    plan's aggregate entries execute sharded (``GridBackend`` under
    ``shard_map`` on the grid mesh), the oracle comparison stays unsharded,
    and the measured cycles come back **per shard**.

    Returns generated ``tokens``, the ``site_backends`` mapping actually
    traced, per-distinct-backend int-GEMM ``rel_rmse`` vs the (unsharded)
    binary oracle, prefill ``drift`` / ``top1_agreement`` vs the float
    model, wall time, the ``grid`` shape (None unsharded), and per-site
    measured/dyn/floor/wc decode-cycle totals (``site_cycles``; for a grid,
    ``{site: {"gx,gy": totals}}``; DLA geometry from the plan's meta).
    """
    grid = plan.grid if isinstance(plan, backends_lib.GridPlan) else None
    entry_plan = plan.aggregate if grid else plan
    # packed: planned sites execute from the bit-packed store (bit-identical
    # codes); reference logits, numerics spot-checks, site discovery and
    # cycle measurement all keep reading the float params, so every evidence
    # field below matches the unpacked replay.
    exec_params = (backends_lib.pack_weights(cfg, params, plan)
                   if packed else params)
    if ref_logits is None:
        ref_logits = prefill_logits(cfg, params, mesh, prompt)
    t0 = time.time()
    with backends_lib.use_plan(plan) as execution:
        tokens = generate(cfg, exec_params, mesh, prompt, max_new)
        exec_logits = prefill_logits(cfg, exec_params, mesh, prompt)
    wall = time.time() - t0
    if not execution.calls:
        raise RuntimeError(
            "plan execution contracted no GEMM sites — do the plan's "
            "patterns match this model's site names?")
    site_backends = {
        c.site: f"{c.backend}@{c.bits}"
        + (f":{c.stream_len}" if getattr(c, "stream_len", 0) else "")
        for c in execution.calls}
    rel_rmse = {}
    for design, bits, stream_len in entry_plan.distinct_engines():
        tag = f"{design}@{bits}" + (f":{stream_len}" if stream_len else "")
        if not any(tag == t for t in site_backends.values()):
            continue
        backend = backends_lib.resolve(design, bits=bits,
                                       stream_len=stream_len or None)
        if grid:
            backend = backends_lib.as_grid(backend, *grid)
        rel_rmse[tag] = validate_backend_numerics(
            params, backend, oracle=_oracle_for(backend))
    ref = np.asarray(ref_logits, np.float32)
    got = np.asarray(exec_logits, np.float32)
    meta = entry_plan.metadata()
    unit_n = int(meta.get("unit_n", 64))
    num_units = int(meta.get("num_units", 64))
    sites = {s.name: s for s in planner_lib.discover_sites(
        cfg, params, batch=prompt.shape[0])}
    site_cycles = {}
    for entry in entry_plan.sites:
        site = sites.get(entry.pattern)
        if site is None or entry.pattern not in site_backends:
            continue
        if grid:
            site_cycles[entry.pattern] = planner_lib.measure_grid_site_cycles(
                site, entry, grid=grid, unit_n=unit_n, num_units=num_units)
        else:
            site_cycles[entry.pattern] = planner_lib.measure_site_cycles(
                site, entry, unit_n=unit_n, num_units=num_units)
    return {
        "tokens": tokens,
        "site_backends": site_backends,
        "wall_s": wall,
        "rel_rmse": rel_rmse,
        "drift": gemm_sims_lib.rel_rmse(got, ref),
        "top1_agreement": float(np.mean(np.argmax(got, -1)
                                        == np.argmax(ref, -1))),
        "grid": grid,
        "site_cycles": site_cycles,
    }


def _parse_stream_lens(spec: str | None) -> tuple[int, ...]:
    """``"16,32,64"`` -> ``(16, 32, 64)`` (empty/None -> no stochastic)."""
    if not spec:
        return ()
    try:
        lens = tuple(int(tok) for tok in spec.split(",") if tok.strip())
    except ValueError:
        raise SystemExit(f"error: --stream-lens must be a comma-separated "
                         f"list of ints, got {spec!r}")
    if any(L < 1 for L in lens):
        raise SystemExit(f"error: stream lengths must be >= 1, got {spec!r}")
    return lens


def run_plan_mode(args, cfg, params) -> int:
    """``serve plan``: derive, save and report a mixed-precision plan."""
    site_list = planner_lib.discover_sites(cfg, params, batch=args.batch)
    stream_lens = _parse_stream_lens(args.stream_lens)
    designs = planner_lib.DEFAULT_DESIGNS
    if stream_lens:
        designs = designs + (planner_lib.STOCHASTIC_DESIGN,)
    plan = planner_lib.build_plan(
        cfg, params, batch=args.batch, unit_n=args.unit_n,
        num_units=args.units, sites=site_list, designs=designs,
        stream_lens=stream_lens)
    path = plan.save(args.plan_out)
    meta = plan.metadata()
    totals = meta["totals"]
    sites = {s.name: s for s in site_list}

    print(f"\n=== backend plan for {args.arch} "
          f"({args.units}x {args.unit_n}x{args.unit_n} units, objective "
          f"{meta['objective']}) ===")
    print(f"{'site':>24s} {'engine':>20s} {'b_spa':>6s} {'dynE_uJ':>9s} "
          f"{'relMSE':>7s} {'measured_cyc':>13s} {'wc_cyc':>10s}")
    for e in plan.sites:
        cyc = planner_lib.measure_site_cycles(
            sites[e.pattern], e, unit_n=args.unit_n, num_units=args.units)
        print(f"{e.pattern:>24s} {e.engine_label:>20s} "
              f"{e.bit_blockmax:6.3f} {e.dyn_energy_uj:9.4f} "
              f"{e.rel_mse:7.4f} {cyc['measured']:13.1f} {cyc['wc']:10.1f}")
    planned = totals["planned"]
    print(f"\nplanned dyn energy {planned['dyn_energy_uj']:.4f} uJ / decode "
          f"step (wc {planned['wc_energy_uj']:.4f} uJ)")
    for name in sorted(totals["uniform"]):
        tot = totals["uniform"][name]
        mark = " <-- best uniform" if name == totals["uniform_best"] else ""
        print(f"  uniform {name:>12s}: dyn {tot['dyn_energy_uj']:.4f} uJ"
              f"{mark}")
    best = totals["uniform_best"]
    if best is not None:
        saving = 1.0 - planned["dyn_energy_uj"] \
            / max(totals["uniform"][best]["dyn_energy_uj"], 1e-30)
        print(f"plan vs best uniform ({best}): {saving:.2%} predicted "
              f"energy saving")
    distinct = plan.distinct_engines()
    print(f"distinct engines chosen: "
          f"{', '.join(f'{d}@{b}' + (f':{L}' if L else '') for d, b, L in distinct)} "
          f"({'mixed' if len(distinct) > 1 else 'uniform'} assignment)")
    print(analysis_verdict(plan, site_names=[s.name for s in site_list]))
    print(f"plan saved to {path} (replay: serve --arch {args.arch}"
          f"{' --smoke' if args.smoke else ''} --backend-plan {path})")
    return 0


def analysis_verdict(plan, site_names=None) -> str:
    """One-line static numeric-safety verdict for a plan.

    Runs ``repro.analysis.plan_lint`` over the plan (against the model's
    site inventory when given, so dead/shadowed patterns and unmatched
    sites are checked too) and renders the findings as the analysis CLI
    would — the serving report carries the same verdict the gate enforces.
    """
    from repro.analysis import findings as findings_lib
    from repro.analysis import plan_lint
    found = plan_lint.lint_plan(plan, site_names=site_names)
    for f in found:
        print(f"  {f.render()}")
    return findings_lib.verdict_line(found)


def run_grid_plan_mode(args, cfg, params, grid: tuple[int, int]) -> int:
    """``serve plan --grid X,Y``: derive, save and report a per-shard plan."""
    site_list = planner_lib.discover_sites(cfg, params, batch=args.batch)
    gplan = planner_lib.build_grid_plan(
        cfg, params, grid=grid, batch=args.batch, unit_n=args.unit_n,
        num_units=args.units, sites=site_list)
    path = gplan.save(args.plan_out)
    meta = gplan.metadata()
    totals = meta["totals"]
    agg = totals["aggregate"]
    sites = {s.name: s for s in site_list}

    print(f"\n=== grid backend plan for {args.arch} "
          f"({grid[0]}x{grid[1]} grid of {args.units}x {args.unit_n}x"
          f"{args.unit_n} nodes, objective {meta['objective']}) ===")
    print("aggregate (executed) assignment, with per-shard measured cycles:")
    for e in gplan.aggregate.sites:
        cyc = planner_lib.measure_grid_site_cycles(
            sites[e.pattern], e, grid=grid, unit_n=args.unit_n,
            num_units=args.units)
        shard_meas = ", ".join(f"{c}:{v['measured']:.0f}"
                               for c, v in sorted(cyc.items()))
        print(f"  {e.pattern:>24s} -> {e.design}@{e.bits} "
              f"(b_spa {e.bit_blockmax:.3f}, dynE {e.dyn_energy_uj:.4f} uJ; "
              f"measured cyc/shard {shard_meas})")
    print("\nper-shard verdicts (each shard plans its own weight slices):")
    for key, _plan in gplan.shards:
        v = totals["per_shard"][key]
        best = v["uniform_best"]
        best_e = v["uniform"][best]["dyn_energy_uj"] if best else 0.0
        print(f"  shard {key}: planned {v['planned']['dyn_energy_uj']:.4f} uJ"
              f" vs best uniform {best} {best_e:.4f} uJ")
    hetero = meta["heterogeneous_sites"]
    print(f"shard-heterogeneous sites: "
          f"{', '.join(hetero) if hetero else 'none'}")
    best = agg["uniform_best"]
    if best is not None:
        best_e = agg["uniform"][best]["dyn_energy_uj"]
        planned = agg["planned"]["dyn_energy_uj"]
        hetero_e = agg["planned_heterogeneous"]["dyn_energy_uj"]
        print(f"aggregate: executed plan {planned:.4f} uJ, per-shard "
              f"heterogeneous {hetero_e:.4f} uJ, best uniform ({best}) "
              f"{best_e:.4f} uJ -> {1.0 - hetero_e / max(best_e, 1e-30):.2%} "
              f"predicted saving")
    print(analysis_verdict(gplan, site_names=[s.name for s in site_list]))
    print(f"grid plan saved to {path} (replay: serve --arch {args.arch}"
          f"{' --smoke' if args.smoke else ''} --backend-plan {path} "
          f"--grid {grid[0]},{grid[1]})")
    return 0


def run_traffic_mode(args, cfg, params, grid, plan) -> int:
    """``serve traffic``: continuous vs static batching on one seeded trace.

    Generates a Poisson traffic trace, serves it twice through the SAME
    :class:`repro.serving.ServingEngine` (same paged pool geometry, same
    backend/plan scope) — once under continuous batching, once under static
    batching — and reports throughput, latency percentiles, batch occupancy
    and Eq.-1 energy per token for both.  Gates (non-zero exit) on:

    * continuous throughput >= static throughput on the same trace,
    * both schedulers completing every request; the per-request token
      streams must also be identical across schedulers — a strict gate on
      the float path and, under --execute-backend/--backend-plan, whenever
      ``--act-scale per-row`` is active (per-row activation quantization
      makes each request's integer codes a pure function of its own
      tokens).  Only under backend execution with the default per-tensor
      scale is the identity check informational: that scale spans the
      whole decode batch, so a request's tokens legitimately depend on
      which requests it is co-batched with,
    * the float model's decode logits — prefill, then paged decode
      teacher-forced with the continuous run's tokens — staying within
      ``repro.serving.logit_check``'s tolerance of the float32 reference
      forward pass, and, when the float model served, that replay
      reproducing every served token (skipped under --grid: the sharded
      variant is covered by the tier-1 subprocess tests).
    """
    from repro.models import common as common_lib
    if args.execute_backend and plan is not None:
        print("error: serve traffic takes --execute-backend OR "
              "--backend-plan, not both")
        return 2
    tcfg = TrafficConfig(num_requests=args.requests,
                         arrival_rate=args.arrival_rate, seed=args.seed)
    trace = generate_trace(tcfg)
    engine_kw = dict(
        max_batch=args.batch, page_size=args.page_size,
        num_pages=args.num_pages, max_seq_len=args.max_seq_len,
        backend=args.execute_backend, plan=plan, bits=args.bits, grid=grid,
        unit_n=args.unit_n, num_units=args.units,
        pricing_design=args.gemm_backend, packed=args.packed)
    engine = ServingEngine(cfg, params, attention=args.decode_attention,
                           **engine_kw)
    scope = (f"plan {args.backend_plan}" if plan is not None
             else f"backend {args.execute_backend}@{args.bits}"
             if args.execute_backend else "float model")
    if args.packed:
        rep = accounting.packed_store_report(engine._exec_params)
        scope += " [packed]"
        print(f"packed weight store: {rep.packed_sites}/{rep.total_sites} "
              f"sites bit-packed, {rep.stored_bytes / 2**20:.2f} MiB vs "
              f"{rep.float32_bytes / 2**20:.2f} MiB fp32 "
              f"({rep.reduction:.2f}x smaller; packed sites alone "
              f"{rep.packed_reduction:.2f}x)")
    print(f"\n=== serving traffic on {args.arch}: {len(trace)} requests "
          f"(Poisson rate {args.arrival_rate}/step, seed {args.seed}), "
          f"{args.batch} slots, {engine.num_pages} pages x {args.page_size} "
          f"slots, {scope}, energy priced on {engine.energy.design} ===")
    with common_lib.activation_scaling(args.act_scale):
        reports = {name: engine.run(trace, name)
                   for name in ("continuous", "static")}
    print(f"{'scheduler':>12s} {'reqs':>5s} {'tokens':>7s} {'steps':>6s} "
          f"{'tok/step':>9s} {'p50':>6s} {'p99':>7s} {'queue':>6s} "
          f"{'occup':>6s} {'uJ/tok':>9s}")
    for name, r in reports.items():
        print(f"{name:>12s} {r.requests:5d} {r.tokens:7d} {r.steps:6d} "
              f"{r.throughput_tok_per_step:9.3f} {r.latency_p50:6.1f} "
              f"{r.latency_p99:7.1f} {r.queue_delay_mean:6.2f} "
              f"{r.occupancy:6.3f} {r.energy_per_token_uj:9.4f}")
    rc, rs = reports["continuous"], reports["static"]
    ok = True
    gain = rc.throughput_tok_per_step / max(rs.throughput_tok_per_step, 1e-30)
    beats = rc.throughput_tok_per_step >= rs.throughput_tok_per_step
    print(f"continuous vs static on the same trace: {gain:.2f}x throughput, "
          f"p99 latency {rc.latency_p99:.0f} vs {rs.latency_p99:.0f} steps")
    if not beats:
        print("WARNING: continuous batching did not beat static batching")
        ok = False
    complete = (rc.requests == len(trace) == rs.requests)
    same_tokens = rc.request_tokens == rs.request_tokens
    quantized = args.execute_backend or plan is not None
    strict = (not quantized) or args.act_scale == "per-row"
    note = ("" if not quantized else
            " (strict: per-row act-quant decouples co-batched rows)"
            if strict else
            " (informational: per-tensor act-quant couples co-batched rows)")
    print(f"all {len(trace)} requests completed under both schedulers: "
          f"{complete}; per-request token streams identical: "
          f"{same_tokens}{note}")
    ok = ok and complete and (same_tokens or not strict)
    if args.decode_attention == "fused":
        # replay the continuous run on the gather oracle.  Informational:
        # the two attention lowerings round differently, and a near-tied
        # argmax or a 4-bit code on a rounding edge then flips a token (at
        # internlm2-1.8b widths on a v5e every request diverges within two
        # tokens); the logit check below holds the fused path instead
        gather_engine = ServingEngine(cfg, params, attention="gather",
                                      **engine_kw)
        with common_lib.activation_scaling(args.act_scale):
            rg = gather_engine.run(trace, "continuous")
        fused_same = rc.request_tokens == rg.request_tokens
        print(f"fused vs gather decode token streams (continuous): "
              f"identical: {fused_same} (informational)")
    if grid is None:
        float_engine = engine if not quantized else ServingEngine(
            cfg, params, attention=args.decode_attention,
            max_batch=args.batch, page_size=args.page_size,
            num_pages=args.num_pages, max_seq_len=args.max_seq_len)
        check = check_decode_logits(
            float_engine, [engine.prompt_tokens(r) for r in trace],
            [rc.request_tokens[r.req_id] for r in trace])
        print(check.line())
        # replaying the float engine's own programs reproduces its tokens
        # by construction (prefill at one fixed shape, row-wise decode)
        ok = ok and check.ok and (quantized or check.replay_agreement == 1.0)
    return 0 if ok else 1


def main() -> int:
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", nargs="?", default="serve",
                    choices=["serve", "plan", "traffic"],
                    help="'serve' generates tokens (default); 'plan' derives "
                         "+ saves a per-layer mixed-precision backend plan "
                         "for the config and reports predicted vs uniform "
                         "energy and measured per-site decode cycles; "
                         "'traffic' serves a seeded Poisson trace through "
                         "the paged continuous-batching engine and compares "
                         "continuous vs static batching")
    ap.add_argument("--arch", default="llama3-8b", choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--gemm-backend", default="tubgemm",
                    choices=["ugemm", "tugemm", "tubgemm", "bgemm"],
                    help="design the pricing table highlights")
    ap.add_argument("--execute-backend", default=None, metavar="SPEC",
                    help="also EXECUTE prefill/decode with every quantized "
                         "dense layer contracted on this backend "
                         "(simulated design, *_pallas kernel mirror, or a "
                         "rate-coded spec like 'ugemm_stochastic:64' where "
                         ":L overrides the stream length); one of "
                         f"{', '.join(backends_lib.available())}")
    ap.add_argument("--backend-plan", default=None, metavar="FILE",
                    help="execute prefill/decode with every dense site "
                         "contracted on the backend its plan entry names "
                         "(a JSON file from 'serve plan' or "
                         "benchmarks.run plan)")
    ap.add_argument("--plan-out", default="reports/plan.json",
                    help="where 'serve plan' saves the derived plan")
    ap.add_argument("--stream-lens", default=None, metavar="L1,L2,...",
                    help="[plan] admit rate-coded ugemm_stochastic "
                         "candidates at these stream lengths, making "
                         "(design, bits, stream_len) the planned assignment "
                         "(e.g. --stream-lens 16,32,64,128)")
    ap.add_argument("--act-scale", default="per-tensor",
                    choices=["per-tensor", "per-row"],
                    help="[traffic] activation quantization granularity "
                         "under backend execution; per-row decouples "
                         "co-batched requests and turns the identical-"
                         "token-stream check into a strict gate")
    ap.add_argument("--bits", type=int, default=4, choices=[2, 4, 8])
    ap.add_argument("--unit-n", type=int, default=128)
    ap.add_argument("--units", type=int, default=64)
    ap.add_argument("--requests", type=int, default=12,
                    help="[traffic] number of requests in the seeded trace")
    ap.add_argument("--arrival-rate", type=float, default=1.0,
                    help="[traffic] Poisson arrivals per scheduler step")
    ap.add_argument("--seed", type=int, default=0,
                    help="[traffic] trace seed (arrivals + lengths)")
    ap.add_argument("--page-size", type=int, default=8,
                    help="[traffic] KV-cache page size in token slots")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="[traffic] KV pool size in pages (default: every "
                         "slot can hold a worst-case request, +1 trash page)")
    ap.add_argument("--max-seq-len", type=int, default=64,
                    help="[traffic] per-request position budget "
                         "(prompt + output)")
    ap.add_argument("--decode-attention", default="fused",
                    choices=["fused", "gather"],
                    help="[traffic] decode attention path: 'fused' walks "
                         "each block table page-by-page with online softmax "
                         "(O(len*KVH) KV traffic; the default), 'gather' "
                         "materializes the padded KV view (the bit-exact "
                         "oracle).  Under 'fused' the continuous run is "
                         "replayed on the gather path and the sampled "
                         "token streams must match exactly whenever the "
                         "scheduler-identity gate is strict")
    ap.add_argument("--packed", action="store_true",
                    help="freeze every planned site's weight bit-packed "
                         "(int32 words, 32/bits codes each) at its assigned "
                         "width and execute from the packed store; "
                         "bit-identical to quantize-then-execute, 4-16x "
                         "fewer weight bytes; needs --execute-backend or "
                         "--backend-plan to fix the widths")
    ap.add_argument("--grid", default=None, metavar="X,Y",
                    help="tensor-parallel PE-array grid: 'plan' derives a "
                         "per-shard heterogeneous GridPlan; execution modes "
                         "shard every dense contraction under shard_map on "
                         "an XxY device mesh (needs X*Y visible devices, "
                         "e.g. XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    args = ap.parse_args()

    if args.packed and not (args.execute_backend or args.backend_plan):
        print("error: --packed needs --execute-backend or --backend-plan "
              "to fix each site's bit-width")
        return 2
    if args.execute_backend:
        # No argparse choices= — the spec grammar ("ugemm_stochastic:64")
        # is the registry's; let resolve() validate it once, up front.
        try:
            backends_lib.resolve(args.execute_backend, bits=args.bits)
        except (KeyError, ValueError) as exc:
            print(f"error: --execute-backend {args.execute_backend!r}: {exc}")
            return 2
    grid = backends_lib.parse_grid(args.grid) if args.grid else None
    plan = None
    if args.backend_plan and args.mode != "plan":
        # Load up front: a GridPlan implies grid execution even without
        # --grid, and the mesh below must match the plan's device needs.
        plan = backends_lib.load_plan(args.backend_plan)
        if isinstance(plan, backends_lib.GridPlan):
            if grid is not None and grid != plan.grid:
                print(f"error: --grid {grid} conflicts with the grid plan's "
                      f"own grid {plan.grid}")
                return 2
            grid = plan.grid
        elif grid is not None:
            # shard a flat plan's sites across the requested grid
            plan = backends_lib.GridPlan(units_x=grid[0], units_y=grid[1],
                                         aggregate=plan, shards=())
    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if cfg.frontend_stub:
        print(f"note: {args.arch} uses a frontend stub; serving raw backbone tokens")
    # Planning is analytic (no grid devices needed); execution with a grid
    # runs the jitted steps on the grid mesh so the in-step shard_maps and
    # the step shardings agree on one device set.
    needs_grid_mesh = grid is not None and args.mode != "plan" \
        and (args.execute_backend or args.backend_plan
             or args.mode == "traffic")
    mesh = (make_grid_mesh(*grid) if needs_grid_mesh
            else single_device_mesh())
    with jax.set_mesh(mesh):
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    if args.mode == "plan":
        if grid is not None:
            return run_grid_plan_mode(args, cfg, params, grid)
        return run_plan_mode(args, cfg, params)
    if args.mode == "traffic":
        return run_traffic_mode(args, cfg, params, grid, plan)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)

    t0 = time.time()
    toks = generate(cfg, params, mesh, prompt, args.tokens)
    wall = time.time() - t0
    print(f"generated {toks.shape} tokens in {wall:.2f}s "
          f"({args.batch * args.tokens / wall:.1f} tok/s on CPU sim)")

    # --- backend numerics: batched engine vs binary oracle on real weights ---
    rel = validate_backend_numerics(params, args.gemm_backend, args.bits)
    tag = "bit-exact" if rel == 0.0 else f"relRMSE {rel:.2e}"
    print(f"backend numerics ({args.gemm_backend}, {args.bits}-bit, "
          f"batched weight tiles): {tag}")

    # --- unary-DLA energy accounting (the paper's technique, end to end) ---
    rec, stats = build_workload(cfg, params, args.batch, args.prompt_len, args.bits)
    agg = sparsity.combine_stats(list(stats.values()))
    print(f"\nweight sparsity ({args.bits}-bit): word={agg.word:.4f} "
          f"bit_elem={agg.bit_elem:.4f} bit_blockmax={agg.bit_blockmax:.4f}")
    print(f"\nper-decode-token DLA cost ({args.units}x {args.unit_n}x{args.unit_n} "
          f"units, {args.bits}-bit):")
    print(f"{'design':>9s} {'wc_energy_uJ':>13s} {'dyn_energy_uJ':>14s} "
          f"{'dyn_latency_us':>15s} {'saving':>7s}")
    costs = {design: backends_lib.resolve(design, bits=args.bits)
             .price(rec.calls, unit_n=args.unit_n, num_units=args.units)
             for design in sweetspot_lib.CALIBRATED_DESIGNS}
    for design, cost in costs.items():
        mark = " <-- selected" if design == args.gemm_backend else ""
        print(f"{design:>9s} {cost.wc_energy_uj:13.2f} {cost.dyn_energy_uj:14.2f} "
              f"{cost.dyn_latency_us:15.2f} {cost.sparsity_saving:6.1%}{mark}")

    # --- sweet-spot verdict for this model's actual layer shapes ------------
    rec_by = sweetspot_lib.recommend_backend(
        rec.calls, bits=args.bits, unit_n=args.unit_n, num_units=args.units,
        costs=costs)
    best_e = rec_by["dyn_energy_uj"]["best"]
    best_l = rec_by["dyn_latency_us"]["best"]
    print(f"\nsweet-spot ({args.bits}-bit, {args.unit_n}x{args.unit_n} units): "
          f"{best_e} minimizes energy, {best_l} minimizes latency "
          f"for this model's layer shapes")
    if args.gemm_backend not in (best_e, best_l):
        e_sel = dict(rec_by["dyn_energy_uj"]["ranking"])[args.gemm_backend]
        e_best = dict(rec_by["dyn_energy_uj"]["ranking"])[best_e]
        print(f"note: selected backend {args.gemm_backend} spends "
              f"{e_sel / e_best:.2f}x the energy of {best_e} here "
              f"(rerun with --gemm-backend {best_e})")

    # --- end-to-end execution on the chosen backend -------------------------
    if args.execute_backend:
        backend = backends_lib.resolve(args.execute_backend, bits=args.bits)
        stream_len = getattr(backend, "stream_len", None)
        if grid is not None:
            backend = backends_lib.as_grid(backend, *grid)
        gtag = (f" on a {grid[0]}x{grid[1]} grid (shard_map, psum over k)"
                if grid else "")
        ltag = f", L={stream_len} bitstreams" if stream_len else ""
        print(f"\n=== executing model on {backend.name} "
              f"({backend.bits}-bit int tiles{ltag}){gtag} ===")
        result = run_backend_execution(
            cfg, params, mesh, prompt, backend, args.tokens,
            unit_n=args.unit_n, num_units=args.units, stats=stats,
            packed=args.packed)
        qt = result["tokens"]
        print(f"generated {qt.shape} tokens in {result['wall_s']:.2f}s; "
              f"{result['sites']} dense GEMM sites contracted on the backend")
        tag = ("bit-exact" if result["rel_rmse"] == 0.0
               else f"relRMSE {result['rel_rmse']:.2e}")
        kind = "exact design" if backend.exact else "stochastic design"
        oracle = ("exact-uGEMM oracle" if result["oracle"] == "ugemm"
                  else "binary oracle")
        print(f"int GEMMs vs {oracle}: {tag} ({kind})")
        print(f"output drift vs float model (prefill logits): "
              f"relRMSE {result['drift']:.3f}, "
              f"top-1 agreement {result['top1_agreement']:.1%}")
        cyc = result["cycles"]
        in_bounds = cyc["dyn_floor"] - 0.5 <= cyc["measured"] <= cyc["wc"] + 0.5
        priced_dyn = costs[backend.pricing_design].dyn_latency_us * 1e3 \
            / ppa.CLOCK_PERIOD_NS * getattr(backend, "cycle_scale", 1.0)
        stag = (f", measured stream relRMSE {result['rel_rmse']:.2e} at "
                f"L={stream_len}" if stream_len else "")
        print(f"per-decode-token cycles ({args.units}x {args.unit_n}x"
              f"{args.unit_n} units): measured {cyc['measured']:.3e} within "
              f"[dyn floor {cyc['dyn_floor']:.3e}, wc {cyc['wc']:.3e}]: "
              f"{in_bounds} (priced Eq.1 dyn {priced_dyn:.3e}{stag})")
        if not in_bounds:
            print("WARNING: measured cycles outside the priced dyn/wc bounds")
            return 1

    # --- end-to-end execution on a per-site mixed-precision plan ------------
    if args.backend_plan:
        is_grid = isinstance(plan, backends_lib.GridPlan)
        distinct = (plan.aggregate if is_grid else plan).distinct_engines()
        gtag = (f" on a {plan.units_x}x{plan.units_y} grid" if is_grid
                else "")
        labels = ", ".join(f"{d}@{b}" + (f":{L}" if L else "")
                           for d, b, L in distinct)
        print(f"\n=== executing model on backend plan {args.backend_plan}"
              f"{gtag} ({labels}) ===")
        print(analysis_verdict(plan))
        result = run_plan_execution(cfg, params, mesh, prompt, plan,
                                    args.tokens, packed=args.packed)
        qt = result["tokens"]
        print(f"generated {qt.shape} tokens in {result['wall_s']:.2f}s; "
              f"{len(result['site_backends'])} dense GEMM sites contracted:")
        for site, tag in sorted(result["site_backends"].items()):
            print(f"  {site:>24s} -> {tag}")
        ok = True
        for tag, rel in sorted(result["rel_rmse"].items()):
            design = tag.split("@")[0]
            exact = backends_lib.resolve(design).exact
            label = "bit-exact" if rel == 0.0 else f"relRMSE {rel:.2e}"
            oracle = "exact-uGEMM oracle" if ":" in tag else "binary oracle"
            if is_grid:
                oracle = "unsharded " + oracle
            print(f"int GEMMs vs {oracle} on {tag}: {label}")
            if exact and rel != 0.0:
                ok = False
        print(f"output drift vs float model (prefill logits): "
              f"relRMSE {result['drift']:.3f}, "
              f"top-1 agreement {result['top1_agreement']:.1%}")
        total = {"measured": 0.0, "dyn": 0.0, "dyn_floor": 0.0, "wc": 0.0}

        def _check(label, cyc):
            in_bounds = (cyc["dyn_floor"] - 0.5 <= cyc["measured"]
                         <= cyc["wc"] + 0.5)
            print(f"  {label:>30s} cycles: measured {cyc['measured']:.3e} in "
                  f"[floor {cyc['dyn_floor']:.3e}, wc {cyc['wc']:.3e}]: "
                  f"{in_bounds} (planned Eq.1 dyn {cyc['dyn']:.3e})")
            return in_bounds

        for site, cyc in sorted(result["site_cycles"].items()):
            if result["grid"]:
                for coord, shard_cyc in sorted(cyc.items()):
                    ok = _check(f"{site} [{coord}]", shard_cyc) and ok
                    for key in total:
                        total[key] += shard_cyc[key]
            else:
                ok = _check(site, cyc) and ok
                for key in total:
                    total[key] += cyc[key]
        scope = "per-shard " if result["grid"] else ""
        print(f"per-decode-token {scope}cycle totals: measured "
              f"{total['measured']:.3e} within [dyn floor "
              f"{total['dyn_floor']:.3e}, wc {total['wc']:.3e}] "
              f"(planned Eq.1 dyn {total['dyn']:.3e})")
        if not ok:
            print("WARNING: plan replay violated bit-exactness or cycle "
                  "bounds")
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
