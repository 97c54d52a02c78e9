"""Summarize the program's own spans in a profiler trace.

    python bench/spandump.py <trace dir>

For reading a traced run by hand: per span name, how many there were,
their summed length and counts (jit traces, compiles, persistent-cache
hits, garbage collections); how far apart the clock offsets of the
mirrored spans lie; how much of the device's idle time lies inside some
span, and inside which; and the decode program's device time per call by
named scope.  ``harness/program.py`` says what the spans and scopes are.
"""

from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path


def summary(pt, stretch: tuple[float, float] | None = None) -> dict:
    """What ``pt`` (a ``harness.program.ProgramTrace``) holds inside
    ``stretch`` (trace clock, ns; by default the device's first to last
    operation)."""
    from harness import program, trace as trace_lib
    dt = pt.device
    lo, hi = stretch or dt.span_ns()
    spans = program.inside(pt.spans, lo, hi)
    phases: dict[str, dict] = defaultdict(lambda: defaultdict(float))
    for s in spans:
        p = phases[s.name]
        p["n"] += 1
        p["ms"] += s.dur_ns * 1e-6
        for k, v in s.counts.items():
            p[k] += v
    idle = trace_lib.gaps(dt.ops_by_device[0], lo, hi)
    idle_ns = sum(b - a for a, b in idle)
    in_span = defaultdict(float)
    for s in spans:
        in_span[s.name] += program.overlap_ns(idle, [(s.start_ns, s.end_ns)])
    offsets = program.clock_offsets(spans)
    runs = dt.programs("_decode_fn")
    names = pt.hlo("_decode_fn")
    picks = {"gemm": program.is_gemm, "pool_copy": program.is_pool_copy,
             "page_walk": program.in_scope("page_walk"),
             "kv_write": program.in_scope("kv_write")}
    decode = {k: program.scope_ms(dt.ops_by_device[0], runs, names, pick)
              for k, pick in picks.items()}
    if runs:
        decode["step"] = 1e3 * sum(r.seconds for r in runs) / len(runs)
    return {
        "stretch_s": (hi - lo) * 1e-9,
        "spans": len(spans),
        "clock_offset_spread_ms": ((max(offsets) - min(offsets)) * 1e-6
                                   if offsets else None),
        "idle_s": idle_ns * 1e-9,
        "idle_in_spans_share": (sum(in_span.values()) / idle_ns
                                if idle_ns and spans else None),
        "idle_s_by_span": {k: v * 1e-9 for k, v in sorted(
            in_span.items(), key=lambda kv: -kv[1]) if v},
        "phases": {k: dict(v) for k, v in sorted(phases.items())},
        "decode_ms_per_call": decode,
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sys.path[:0] = [str(Path(__file__).resolve().parent),
                    str(Path(__file__).resolve().parents[1] / "src")]
    from harness import program, trace as trace_lib
    path = program.newest(argv[0])
    from jax.profiler import ProfileData
    dt = trace_lib.from_profile(ProfileData.from_file(path), 0.0)
    pt = program.ProgramTrace(program.load_spans(path), dt, path)
    print(json.dumps(summary(pt), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
