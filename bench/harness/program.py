"""The program's own spans in a profiler trace, joined to the device's work.

While a profiler records, ``ServingEngine.run`` mirrors each leaf span of
its loop onto the profiler's host line (``repro/serving/spans.py``): an
event named after the span that carries ``span_id``, ``step``, ``host_ns``
(its start on ``time.perf_counter_ns``) and, once it ended, its counts.
The decode step's HLO carries named scopes in its ``op_name`` metadata:
``decode_layers`` around the layer scan, the site scopes (``layers/attn/wq``,
``layers/mlp/w_up``, ``lm_head`` ...) around the weight GEMMs, ``kv_write``
and ``page_walk`` around the KV write and the page walk.

This module finds the mirrored spans, joins the two clocks through
``host_ns``, attributes each run of a device program to the span that
dispatched it, and splits the decode program's device time by scope.  A
trace of a program that mirrors no span, or whose HLO has no scopes, gives
empty results, and the metrics that read them read nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import statistics

from harness import hlo, trace as trace_lib

#: the host calls that hand a compiled program to the device (TPU, CPU)
EXECUTE_EVENTS = ("PJRT_LoadedExecutable_Execute",
                  "PjRtCpuExecutable::Execute")
#: the spans in which the host waits on the device
DEVICE_WAITS = ("decode.read_tokens", "admit.first_token")
#: leaf names of the weight GEMM sites, as scopes of the decode HLO
GEMM_SITES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head")
#: roots of the site scopes
SITE_ROOTS = ("layers", "lm_head")
#: HLO ops whose device events enclose the events of the ops they call
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Span:
    """One mirrored leaf span, on the trace's clock."""
    name: str
    start_ns: float
    end_ns: float
    id: int
    step: int
    host_ns: int
    counts: dict

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def spans_of(events) -> list[Span]:
    """The mirrored spans among host events given as ``(name, start_ns,
    duration_ns, stats)``, in time order."""
    out = []
    for name, start, dur, stats in events:
        st = dict(stats)
        if "span_id" not in st or "host_ns" not in st:
            continue
        counts = {k: float(v) for k, v in st.items()
                  if k not in ("span_id", "step", "host_ns")}
        out.append(Span(name, float(start), float(start) + float(dur),
                        int(st["span_id"]), int(st.get("step", -1)),
                        int(st["host_ns"]), counts))
    return sorted(out, key=lambda s: s.start_ns)


def clock_offsets(spans) -> list[float]:
    """Per span, the trace's clock less the host's (ns) at its start."""
    return [s.start_ns - s.host_ns for s in spans]


def to_trace_clock(spans, host_s: float) -> float:
    """A ``time.perf_counter()`` reading, on the trace's clock (ns)."""
    return host_s * 1e9 + statistics.median(clock_offsets(spans))


def inside(spans, lo: float, hi: float) -> list[Span]:
    return [s for s in spans if s.start_ns >= lo and s.end_ns <= hi]


def _program(name: str) -> str:
    """``jit_scatter(123)`` and ``PjitFunction(scatter)`` -> ``scatter``."""
    if name.startswith("PjitFunction("):
        return name[len("PjitFunction("):-1]
    name = name.split("(")[0]
    return name[len("jit_"):] if name.startswith("jit_") else name


def executes_of(host: list[trace_lib.Event]) -> list[trace_lib.Event]:
    """The host's program dispatches in time order, each named after the
    jitted function whose call encloses it (``""`` when none does)."""
    calls = sorted((e for e in host if e.name.startswith("PjitFunction(")),
                   key=lambda e: e.start_ns)
    starts = [c.start_ns for c in calls]
    out = []
    for e in sorted((e for e in host if e.name in EXECUTE_EVENTS),
                    key=lambda e: e.start_ns):
        name = ""
        for c in reversed(calls[:bisect.bisect_right(starts, e.start_ns)]):
            if c.end_ns >= e.end_ns:
                name = _program(c.name)
                break
        out.append(trace_lib.Event(name, e.start_ns, e.dur_ns))
    return out


def dispatches(executes, runs, most_early: int = 8) -> list[tuple]:
    """``(execute, run)`` pairs: each run of a device program joined to
    the host call that dispatched it.

    One queue runs programs in the order the host dispatched them, so the
    two sequences pair off in order, but for up to ``most_early`` runs at
    the start that were dispatched before the trace began.  The offset is
    the one under which most pairs name the same program.  Times do not
    decide it: a device's run can read a little earlier than its dispatch
    (the two planes' clocks differ by up to ~1 ms on a v5e), and an eager
    call may name its program otherwise (``squeeze`` runs as
    ``jit_dynamic_slice``).
    """
    executes = sorted(executes, key=lambda e: e.start_ns)
    runs = sorted(runs, key=lambda r: r.start_ns)
    names = [_program(r.name) for r in runs]

    def agree(k):
        return sum(1 for e, n in zip(executes, names[k:]) if e.name == n)

    best = max(range(min(most_early, len(runs)) + 1),
               key=lambda k: (agree(k), -k))
    return list(zip(executes, runs[best:]))


def runs_in(pairs, spans) -> dict[int, list]:
    """Per span id, the device runs dispatched while the span was open."""
    out = {s.id: [] for s in spans}
    starts = [s.start_ns for s in spans]
    for e, run in pairs:
        k = bisect.bisect_right(starts, e.start_ns) - 1
        if k >= 0 and e.end_ns <= spans[k].end_ns:
            out[spans[k].id].append(run)
    return out


def overlap_ns(intervals, others) -> float:
    """Length of the intersection of two unions of intervals."""
    a = _merge(intervals)
    b = _merge(others)
    total, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            total += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
    return total


def _merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def step_self_ns(spans) -> list[float]:
    """Per step with a successor in ``spans``: its length (its first span's
    start to the next step's) less the time its spans waited on the
    device."""
    first: dict[int, float] = {}
    waits: dict[int, float] = {}
    for s in spans:
        first[s.step] = min(first.get(s.step, s.start_ns), s.start_ns)
        if s.name in DEVICE_WAITS:
            waits[s.step] = waits.get(s.step, 0.0) + s.dur_ns
    return [first[k + 1] - first[k] - waits.get(k, 0.0)
            for k in sorted(first) if k + 1 in first]


def _components(op_name: str) -> list[str]:
    return op_name.split("/")


def is_gemm(op_name: str) -> bool:
    return any(c in GEMM_SITES for c in _components(op_name))


def is_pool_copy(op_name: str) -> bool:
    """Under the layer scan and under no site scope: the scan's own slicing
    of each layer's inputs and stacking of its outputs."""
    parts = _components(op_name)
    return "decode_layers" in parts and not any(c in SITE_ROOTS
                                               for c in parts)


def in_scope(name: str):
    return lambda op_name: name in _components(op_name)


def scope_ms(ops, runs, names: dict, pick) -> float | None:
    """Device time per run, in ms, of the ops inside ``runs`` whose
    ``op_name`` (by ``names``: instruction name -> (opcode, op_name))
    ``pick`` accepts; ``None`` when no op is accepted."""
    if not runs or not names:
        return None
    ops = sorted(ops, key=lambda e: e.start_ns)
    starts = [e.start_ns for e in ops]
    total, hit = 0.0, False
    for run in runs:
        for e in ops[bisect.bisect_left(starts, run.start_ns):
                     bisect.bisect_right(starts, run.end_ns)]:
            opcode, op_name = names.get(
                e.name.split(" = ")[0].lstrip("%"), ("", ""))
            if opcode in CONTAINERS or not pick(op_name):
                continue
            total += e.dur_ns
            hit = True
    return 1e-6 * total / len(runs) if hit else None


@dataclasses.dataclass
class ProgramTrace:
    """The mirrored spans of a traced stretch, its device trace, and the
    file both came from."""
    spans: list[Span]
    device: trace_lib.DeviceTrace
    path: str

    @functools.cached_property
    def pairs(self) -> list[tuple]:
        return dispatches(executes_of(self.device.host),
                          self.device.programs_by_device[0])

    def stretch(self, profile) -> tuple[float, float]:
        """The profiled stretch of a window (``Served.profile``: host
        start, host stop, directory), on the trace's clock."""
        return (to_trace_clock(self.spans, profile[0]),
                to_trace_clock(self.spans, profile[1]))

    def hlo(self, fragment: str) -> dict:
        """Instruction names of the first program holding ``fragment``."""
        found = hlo.op_names(self.path, fragment)
        return next(iter(found.values())) if found else {}


def newest(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


@functools.lru_cache(maxsize=1)
def load_spans(path: str) -> list[Span]:
    """The mirrored spans of the trace file at ``path``."""
    from jax.profiler import ProfileData
    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns, e.stats)
                            for e in line.events)
    return spans_of(host)


def of(reading) -> ProgramTrace | None:
    """The program trace of a traced reading; ``None`` without a trace."""
    if reading.trace is None or reading.served.profile is None:
        return None
    path = newest(reading.served.profile[2])
    return ProgramTrace(load_spans(path), reading.trace, path)
