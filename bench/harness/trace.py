"""Reduction of a profiler trace (``.xplane.pb``) to device times.

On a TPU every chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops``
holds each operation the chip ran (named by its HLO instruction, a
Pallas kernel by its jitted wrapper, e.g. ``_fused_decode_pallas``) and
whose line ``XLA Modules`` holds each run of a compiled program (named
after the jitted function, e.g. ``jit__decode_fn``).  On the CPU, where the tests record their small
traces, operations are events on the host threads that carry an
``hlo_module`` stat; each run of a program is then the span of its
operations between two runs of other programs.  Host threads carry the
spans of what the host was doing (JAX's dispatch, the benchmark's own
annotations), which name the device's idle gaps.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict

TPU_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
#: host spans that enclose whole stretches and so name no particular gap
ENCLOSING = ("ProfileSession", "TraceMe", "$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def seconds(self) -> float:
        return self.dur_ns * 1e-9


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, -float("inf")
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.end_ns <= end:
            continue
        total += e.end_ns - max(e.start_ns, end)
        end = e.end_ns
    return total


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals inside [lo, hi] between the events' intervals."""
    out, end = [], lo
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.start_ns > end:
            out.append((end, min(e.start_ns, hi)))
        end = max(end, e.end_ns)
        if end >= hi:
            break
    if end < hi:
        out.append((end, hi))
    return [(a, b) for a, b in out if b > a]


class DeviceTrace:
    """The device operations, program runs and host spans of one trace."""

    def __init__(self, ops_by_device: list[list[Event]],
                 programs_by_device: list[list[Event]], host: list[Event],
                 window_s: float) -> None:
        self.ops_by_device = ops_by_device
        self.programs_by_device = programs_by_device
        self.host = host
        self.window_s = window_s

    def ops(self, fragment: str) -> list[Event]:
        """Operations of the first chip whose name or program holds
        ``fragment``."""
        return [e for e in self.ops_by_device[0]
                if fragment in e.name or fragment in e.module]

    def programs(self, fragment: str) -> list[Event]:
        """Runs on the first chip of programs whose name holds ``fragment``."""
        return [e for e in self.programs_by_device[0] if fragment in e.name]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [union_ns(ops) for ops in self.ops_by_device]
        return sum(per) / len(per) * 1e-9

    def span_ns(self) -> tuple[float, float]:
        ops = self.ops_by_device[0]
        return (min(e.start_ns for e in ops), max(e.end_ns for e in ops))

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, and the longest idle
        gaps on the first chip named by the host span that covers most of
        each."""
        by_op = defaultdict(float)
        for e in self.ops_by_device[0]:
            by_op[e.name.split(" = ")[0]] += e.dur_ns
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        lo, hi = self.span_ns()
        idle = sorted(gaps(self.ops_by_device[0], lo, hi),
                      key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, v * 1e-9] for n, v in ops],
                "idle_gaps": [[self.host_during(a, b), (b - a) * 1e-9]
                              for a, b in idle]}

    def host_during(self, a: float, b: float) -> str:
        best, cover = "nothing traced", 0.0
        for e in self.host:
            if e.name.startswith(ENCLOSING):
                continue
            c = min(b, e.end_ns) - max(a, e.start_ns)
            if c > cover:
                best, cover = e.name, c
        return best


def _stat(event, key: str) -> str:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return ""


def _events(line) -> list[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns),
                  _stat(e, "hlo_module")) for e in line.events]


def _cpu_programs(ops: list[Event]) -> list[Event]:
    """Runs of programs from CPU operations: maximal stretches of one
    program's operations, in time order."""
    runs: list[Event] = []
    for e in sorted(ops, key=lambda e: e.start_ns):
        last = runs[-1] if runs else None
        if last is not None and last.name == e.module \
                and e.start_ns <= last.end_ns + 1e6:
            runs[-1] = Event(last.name, last.start_ns,
                             max(last.end_ns, e.end_ns) - last.start_ns)
        else:
            runs.append(Event(e.module, e.start_ns, e.dur_ns))
    return runs


def from_profile(pd, window_s: float, devices: int = 1) -> DeviceTrace:
    tpus = sorted((p for p in pd.planes if p.name.startswith(TPU_PLANE)),
                  key=lambda p: p.name)[:devices]
    host: list[Event] = []
    cpu_ops: list[Event] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in _events(line):
                (cpu_ops if e.module else host).append(e)
    if tpus:
        ops, programs = [], []
        for plane in tpus:
            lines = {line.name: line for line in plane.lines}
            ops.append(_events(lines[OPS_LINE]) if OPS_LINE in lines else [])
            programs.append(_events(lines[PROGRAMS_LINE])
                            if PROGRAMS_LINE in lines else [])
    else:
        ops, programs = [cpu_ops], [_cpu_programs(cpu_ops)]
    if not any(ops):
        raise ValueError("the trace holds no device operation")
    # the device may run a little past the host's reading of the stretch
    lo, hi = (min(e.start_ns for e in ops[0]), max(e.end_ns for e in ops[0]))
    return DeviceTrace(ops, programs, host, max(window_s, (hi - lo) * 1e-9))


def load(trace_dir: str, window_s: float, devices: int = 1) -> DeviceTrace:
    """The newest trace under ``trace_dir``, reduced."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]), window_s, devices)
