import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and extract memory / cost / collective statistics.

The two lines above MUST stay the first statements in this module — jax locks
the device count on first init, and the dry-run needs 512 placeholder CPU
devices to build the 16x16 (single-pod) and 2x16x16 (multi-pod) meshes.
Do NOT set that flag anywhere else (tests/benchmarks see the 1 real device).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod|--both]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

Writes one JSON per cell to --out (consumed by benchmarks/roofline.py and
EXPERIMENTS.md §Dry-run/§Roofline).
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch import hlo_cost, hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.launch import steps as steps_lib
from repro.models import model as model_lib
from repro.models.common import ParamDef
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig


def _param_sizes(cfg: ModelConfig):
    """(total, matmul_active) parameter counts from defs (no allocation)."""
    defs = model_lib.model_defs(cfg)
    total = active = 0.0
    expert_frac = None
    if cfg.is_moe:
        expert_frac = cfg.moe.top_k / cfg.moe.num_experts
    flat = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))[0]
    for path, d in flat:
        names = [str(getattr(p, "key", "")) for p in path]
        sz = 1.0
        for s in d.shape:
            sz *= s
        total += sz
        if len(d.shape) < 2:
            continue
        if "embed" in names and not cfg.tie_embeddings:
            continue  # lookup table: no matmul flops (lm_head counted separately)
        frac = 1.0
        if expert_frac is not None and "moe" in names and names[-1] in (
                "w_gate", "w_up", "w_down") and "shared" not in names:
            frac = expert_frac
        active += sz * frac
    return total, active


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    """Assignment convention: 6·N·D train / 2·N·D inference (N = active)."""
    sh = configs.SHAPES[shape_name]
    _, active = _param_sizes(cfg)
    if sh["step"] == "train":
        return 6.0 * active * sh["global_batch"] * sh["seq_len"]
    if sh["step"] == "prefill":
        return 2.0 * active * sh["global_batch"] * sh["seq_len"]
    return 2.0 * active * sh["global_batch"]  # decode: one token per request


def lower_cell(cfg: ModelConfig, shape_name: str, mesh):
    """Build the right step and .lower() it with ShapeDtypeStruct inputs."""
    sh = configs.SHAPES[shape_name]
    b, s = sh["global_batch"], sh["seq_len"]
    ins = steps_lib.input_specs(cfg, shape_name)
    if sh["step"] == "train":
        opt_cfg = AdamWConfig(state_dtype="bfloat16" if cfg.fsdp else "float32")
        step = steps_lib.make_train_step(cfg, mesh, opt_cfg, batch_size=b)
        state_shapes = jax.eval_shape(
            lambda: steps_lib.init_train_state(cfg, opt_cfg, jax.random.PRNGKey(0)))
        return step.lower(state_shapes, ins)
    params = jax.eval_shape(lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    caches = steps_lib.cache_input_specs(cfg, b, s)
    if sh["step"] == "prefill":
        step = steps_lib.make_prefill_step(cfg, mesh, batch_size=b, max_len=s)
        return step.lower(params, ins, caches)
    step = steps_lib.make_decode_step(cfg, mesh, batch_size=b, max_len=s)
    return step.lower(params, ins["tokens"], caches, ins["cache_pos"])


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str | None,
             ep_impl: str | None = None) -> dict:
    cfg = configs.get_config(arch)
    if ep_impl and cfg.is_moe:
        import dataclasses as dc
        cfg = cfg.replace(moe=dc.replace(cfg.moe, ep_impl=ep_impl))
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
           "ep_impl": ep_impl or (cfg.moe.ep_impl if cfg.is_moe else None)}
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = lower_cell(cfg, shape_name, mesh)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

        ca = compiled.cost_analysis() or {}
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float)) and k in
                                ("flops", "bytes accessed", "transcendentals",
                                 "optimal_seconds", "utilization")}
        try:
            ma = compiled.memory_analysis()
            rec["memory_analysis"] = {
                a: int(getattr(ma, a)) for a in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(ma, a)}
        except Exception as e:  # noqa: BLE001 — backend-dependent
            rec["memory_analysis"] = {"error": str(e)}

        hlo = compiled.as_text()
        # XLA's cost_analysis counts while-loop (scan) bodies once; the
        # trip-count-aware analyzer (hlo_cost) is the roofline source.
        # Both are recorded; the discrepancy == scan undercount.
        hc = hlo_cost.analyze(hlo)
        rec["hlo_cost"] = {"flops": hc.flops, "bytes": hc.bytes_accessed,
                           "collective_bytes": hc.collective_bytes,
                           "coll_by_op": hc.coll_by_op,
                           "coll_counts": hc.coll_counts}
        mf = model_flops(cfg, shape_name)
        coll = hlo_stats.CollectiveStats(total_bytes=hc.collective_bytes,
                                         by_op=hc.coll_by_op,
                                         counts={k: int(v) for k, v in
                                                 hc.coll_counts.items()})
        terms = hlo_stats.roofline(
            {"flops": hc.flops, "bytes accessed": hc.bytes_accessed},
            coll, chips, mf)
        rec["roofline"] = {
            "compute_s": terms.compute_s, "memory_s": terms.memory_s,
            "collective_s": terms.collective_s, "dominant": terms.dominant,
            "model_flops": mf,
            "useful_flops_ratio": terms.useful_flops_ratio,
            "roofline_fraction": terms.roofline_fraction,
            "step_time_s": terms.step_time_s,
        }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{rec['mesh']}"
        if ep_impl:
            tag += f"_{ep_impl}"
        with open(os.path.join(out_dir, tag.replace("/", "-") + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=list(configs.ARCH_IDS))
    ap.add_argument("--shape", default=None, choices=list(configs.SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--ep-impl", default=None, choices=["psum", "a2a"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.all:
        cells = configs.cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        if not configs.shape_applicable(configs.get_config(args.arch), args.shape):
            print(f"SKIP {args.arch} x {args.shape}: long_500k needs "
                  "sub-quadratic attention (see DESIGN.md)")
            return 0
        cells = [(args.arch, args.shape)]

    pods = [False, True] if args.both else [args.multi_pod]
    failures = 0
    for arch, shape in cells:
        for mp in pods:
            tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                rec = run_cell(arch, shape, mp, args.out, args.ep_impl)
                r = rec["roofline"]
                print(f"OK   {tag}: compile={rec['compile_s']}s "
                      f"dominant={r['dominant']} "
                      f"terms=({r['compute_s']:.2e},{r['memory_s']:.2e},"
                      f"{r['collective_s']:.2e})s "
                      f"useful={r['useful_flops_ratio']:.2f}", flush=True)
            except Exception:
                failures += 1
                print(f"FAIL {tag}\n{traceback.format_exc()}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
