"""From one served window to the contract's result line.

Each metric named in ``BENCHMARK.json`` is read by its own file,
``metrics/<name>.py``, whose ``read(reading)`` returns a number or ``None``
when it finds nothing to read (the metric is then left out of the line).
With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, the device's busy and window seconds
and a breakdown of the traced stretch.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import math
from pathlib import Path

from harness import correct, counts, serve, trace as trace_lib

METRICS_DIR = Path(__file__).resolve().parents[1] / "metrics"


@dataclasses.dataclass
class Reading:
    """What a metric's reader may read."""
    cell: object
    served: serve.Served
    device_kind: str
    trace: trace_lib.DeviceTrace | None = None

    @property
    def peak(self) -> dict:
        return counts.peaks(self.device_kind)

    def due_at(self) -> dict:
        return {a.req_id: self.served.start + a.due_s
                for a in self.served.arrivals}

    def due_before_trace(self) -> dict:
        """``due_at`` of the requests due before the profiler started."""
        if self.served.profile is None:
            return {}
        a = self.served.profile[0]
        return {r: t for r, t in self.due_at().items() if t < a}

    def profiled_steps(self) -> list:
        """Decode steps whose tokens reached the host while profiling."""
        if self.served.profile is None:
            return []
        a, b, _ = self.served.profile
        return [s for s in self.served.scheduler.decode_steps if a <= s[0] <= b]


def reader(name: str):
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def applies(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def read_metrics(bench: dict, kind: str, reading: Reading) -> dict:
    out = {}
    for m in bench[kind]:
        if not applies(m, reading.cell.name):
            continue
        value = reader(m["name"])(reading)
        if value is None or not math.isfinite(value):
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def tails(served) -> dict:
    """More percentiles of the window's latencies, for reading its spread."""
    from harness import timeline
    s = served.scheduler
    due = {a.req_id: served.start + a.due_s for a in served.arrivals}
    ttft = timeline.ttfts(due, s.first_at)
    gaps = timeline.token_gaps(s.token_at, served.start, served.seconds)
    out = {f"ttft_p{q}_s": timeline.percentile(ttft, q) for q in (50, 75, 90)}
    out.update({f"itl_p{q}_s": timeline.percentile(gaps, q)
                for q in (50, 90, 95)})
    return {k: v for k, v in out.items() if math.isfinite(v)}


def run(bench: dict, cell, seed: int, seconds: float, traced: bool, *,
        t_proc: float, trace_root: Path) -> dict:
    trace_dir = str(Path(trace_root) / f"{cell.name}.{seed}") if traced \
        else None
    served, params = serve.serve(cell, seed, seconds, t_proc=t_proc,
                                 trace_dir=trace_dir)
    device = serve.device_info()
    reading = Reading(cell=cell, served=served, device_kind=device["kind"])
    extra = {}
    if traced:
        if served.profile is None:
            raise RuntimeError("the window closed before the profiler ran")
        start, stop, path = served.profile
        dt = trace_lib.load(path, stop - start, cell.chips)
        reading.trace = dt
        metrics = read_metrics(bench, "per_layer", reading)
        device["busy_s"] = dt.busy_s
        device["window_s"] = dt.window_s
        extra["breakdown"] = dt.breakdown()
    else:
        metrics = read_metrics(bench, "end_to_end", reading)
    verdict = correct.compare(cell, params, seed, served)
    return {"correct": verdict["correct"],
            "attempted": len(served.arrivals),
            "failed": len(served.arrivals) - len(served.finished),
            "metrics": metrics, "device": device, **extra,
            "notes": {"compiles_in_window": served.compiles_in_window,
                      "tails": tails(served),
                      "setup": served.setup,
                      "sampled": verdict["sampled"]},
            "checks": verdict["checks"]}
