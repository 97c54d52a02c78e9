"""Continuous-batching serving benchmark: serves one seeded Poisson trace
through the paged-KV engine under tubGEMM execution, once with continuous
batching and once with static batching, and emits ``reports/serving.json`` +
``reports/serving.md``.

The paper's energy story under *traffic* rather than a single batched call:
every decode step contracts the smoke model's dense sites on the unary
backend (``use_backend`` scope inside ``repro.serving.ServingEngine``) while
the scheduler joins/evicts requests at step boundaries, and each step is
priced with Eq. 1-scaled dynamic energy so the report carries µJ/token
alongside throughput and latency percentiles.

Derived error (the ``benchmarks.run`` quality column) is 0.0 when the run
holds the acceptance properties, +1.0 for each violation:

* continuous batching's token throughput ≥ static batching's on the SAME
  trace (the tentpole gate);
* both schedulers complete every request (the per-request token streams are
  reported but NOT gated here: under backend execution the per-tensor
  activation-quantization scale spans the whole decode batch, so a request's
  tokens legitimately depend on which requests it is co-batched with — the
  float-path schedule-invariance gate lives in ``serve traffic`` and the
  tier-1 tests);
* the paged decode step is bit-exact with the contiguous
  ``model_lib.decode_step`` reference at fp32, and the engine's bucketed
  prefill within ``PREFILL_LOGIT_TOL`` of the contiguous prefill
  (``repro.serving.paged_vs_contiguous_probe``).
"""

from __future__ import annotations

import dataclasses
import json
import os

ARCH = "llama3-8b"
MAX_BATCH = 4
PAGE_SIZE = 8
UNIT_N = 64
NUM_UNITS = 64
BITS = 4


def _markdown(tcfg, reports, probe) -> str:
    rc, rs = reports["continuous"], reports["static"]
    gain = rc.throughput_tok_per_step / max(rs.throughput_tok_per_step, 1e-30)
    decode = ("bit-exact" if probe.decode == 0.0
              else f"max |diff| {probe.decode:.3e}")
    lines = [
        "# Serving under traffic: continuous vs static batching",
        "",
        f"Seeded Poisson trace: {tcfg.num_requests} requests at "
        f"{tcfg.arrival_rate}/step (seed {tcfg.seed}), served on a "
        f"{MAX_BATCH}-slot paged engine ({rc.num_pages} pages x "
        f"{rc.page_size} slots), decode executed on "
        f"{rc.design}@{rc.bits} with Eq.-1 energy accounting.",
        "",
        "| scheduler | requests | tokens | steps | tok/step | p50 | p99 "
        "| queue | occupancy | uJ/token |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for name in ("continuous", "static"):
        r = reports[name]
        lines.append(
            f"| {name} | {r.requests} | {r.tokens} | {r.steps} "
            f"| {r.throughput_tok_per_step:.3f} | {r.latency_p50:.1f} "
            f"| {r.latency_p99:.1f} | {r.queue_delay_mean:.2f} "
            f"| {r.occupancy:.3f} | {r.energy_per_token_uj:.4f} |")
    lines += [
        "",
        f"Continuous batching: {gain:.2f}x throughput, p99 latency "
        f"{rc.latency_p99:.0f} vs {rs.latency_p99:.0f} steps, "
        f"{rc.energy_per_token_uj:.4f} vs {rs.energy_per_token_uj:.4f} "
        "uJ/token on the same trace.",
        f"Paged decode vs contiguous `decode_step` (fp32): {decode}; "
        f"bucketed prefill vs contiguous prefill: max |diff| "
        f"{probe.prefill:.3e}.",
        "",
    ]
    return "\n".join(lines)


def serving(out_dir: str | None = None):
    """Returns (rows, err) per the benchmarks.run contract; writes the files."""
    import jax

    from repro import configs
    from repro.models import model as model_lib
    from repro.serving import (PREFILL_LOGIT_TOL, ServingEngine,
                               TrafficConfig, generate_trace,
                               paged_vs_contiguous_probe)

    out_dir = out_dir or os.environ.get("SERVING_OUT", "reports")
    cfg = dataclasses.replace(configs.get_smoke_config(ARCH),
                              compute_dtype="float32", param_dtype="float32")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    tcfg = TrafficConfig(num_requests=12, arrival_rate=1.0, seed=0)
    trace = generate_trace(tcfg)
    engine = ServingEngine(cfg, params, max_batch=MAX_BATCH,
                           page_size=PAGE_SIZE, backend="tubgemm", bits=BITS,
                           unit_n=UNIT_N, num_units=NUM_UNITS)
    reports = {name: engine.run(trace, name)
               for name in ("continuous", "static")}
    probe = paged_vs_contiguous_probe(cfg, params, page_size=PAGE_SIZE)

    rc, rs = reports["continuous"], reports["static"]
    gain = rc.throughput_tok_per_step / max(rs.throughput_tok_per_step, 1e-30)
    complete = rc.requests == len(trace) == rs.requests
    same_tokens = rc.request_tokens == rs.request_tokens

    os.makedirs(out_dir, exist_ok=True)
    json_path = os.path.join(out_dir, "serving.json")
    with open(json_path, "w") as fh:
        json.dump({
            "arch": ARCH, "traffic": dataclasses.asdict(tcfg),
            "continuous": rc.to_dict(), "static": rs.to_dict(),
            "throughput_gain": gain, "all_completed": complete,
            "token_streams_identical": same_tokens,
            "paged_probe_max_abs_diff": probe.decode,
            "prefill_probe_max_abs_diff": probe.prefill,
        }, fh, indent=2)
    md_path = os.path.join(out_dir, "serving.md")
    with open(md_path, "w") as fh:
        fh.write(_markdown(tcfg, reports, probe))

    rows = []
    for name in ("continuous", "static"):
        r = reports[name]
        rows += [
            (f"{name}_throughput_tok_per_step",
             f"{r.throughput_tok_per_step:.3f}", None),
            (f"{name}_latency_p50_steps", f"{r.latency_p50:.1f}", None),
            (f"{name}_latency_p99_steps", f"{r.latency_p99:.1f}", None),
            (f"{name}_occupancy", f"{r.occupancy:.3f}", None),
            (f"{name}_energy_per_token_uj",
             f"{r.energy_per_token_uj:.4f}", None),
        ]
    rows += [
        ("continuous_vs_static_throughput", f"{gain:.2f}x", None),
        ("all_requests_completed", str(complete), None),
        ("token_streams_identical", str(same_tokens), None),
        ("paged_vs_contiguous_max_abs_diff", f"{probe.decode:.3e}", None),
        ("prefill_vs_contiguous_max_abs_diff", f"{probe.prefill:.3e}", None),
        ("json", json_path, None),
        ("markdown", md_path, None),
    ]
    err = 0.0
    if rc.throughput_tok_per_step < rs.throughput_tok_per_step:
        err += 1.0  # continuous batching must not lose to static batching
    if not complete:
        err += 1.0  # every request must be served to completion
    if probe.decode != 0.0:
        err += 1.0  # paged decode must match the contiguous path bit-for-bit
    if probe.prefill > PREFILL_LOGIT_TOL:
        err += 1.0  # bucketed prefill: fp32 reassociation only
    return rows, err
