"""In-memory spans and counters of the serving loop, on the profiler's clock.

A :class:`SpanRecorder` keeps every span of a ``ServingEngine.run`` in
Python lists and hands them out only on :meth:`SpanRecorder.dump`.  A span
has a name, a start and an end on ``time.perf_counter_ns``, an id, the id of
the span open around it, the request ids it concerns, and a small dict of
counts.  Per-request events (``admitted``, ``first_token``, ``evicted``) are
stamped on the same clock.

Each *leaf* span (one that opens no span inside it) is also entered as a
``jax.profiler.TraceAnnotation`` carrying its id, its step and its host
start (``span_id``, ``step``, ``host_ns``), and leaves its counts on the
annotation when it ends.  A profiler trace then holds the program's phases
on its host line, on the trace's clock: an operation on the device can be
put beside what the host was doing, and ``host_ns`` against the event's
start gives the offset of the two clocks.  Enclosing spans (``serve.run``,
``serve.step``) are not mirrored: a trace viewer that names an idle gap by
the host event covering most of it would name every gap ``serve.step``.

Counters are attributed to the innermost open span of the live recorder:
jit traces, backend compiles (a persistent-cache load counts as one),
persistent-cache hits and garbage collections with their pause, all
through one process-wide listener registered when the first recorder is
made.

:data:`NULL` records nothing: its ``span()`` returns one shared no-op
context manager, so a loop that is not traced allocates nothing, enters no
annotation and leaves the listener idle.
"""

from __future__ import annotations

import gc
import threading
import time

import jax

__all__ = ["NULL", "NullRecorder", "SpanRecorder", "follow_profiler"]

JIT_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def count(self, **counts) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullRecorder:
    """The recorder of an untraced loop: every call does nothing."""

    on = False

    def span(self, name: str, *, req=(), leaf: bool = True, **counts):
        return _NULL_SPAN

    def step_span(self, step: int):
        return _NULL_SPAN

    def event(self, name: str, req: int) -> None:
        pass


NULL = NullRecorder()


class _Span:
    __slots__ = ("rec", "id", "leaf", "annotation")

    def __init__(self, rec: "SpanRecorder", sid: int, leaf: bool):
        self.rec = rec
        self.id = sid
        self.leaf = leaf
        self.annotation = None

    def __enter__(self):
        rec = self.rec
        rec._open.append(self.id)
        if len(rec._open) == 1:
            _LIVE.append(rec)
        start = time.perf_counter_ns()
        if self.leaf:
            self.annotation = jax.profiler.TraceAnnotation(
                rec.names[self.id], span_id=self.id, step=rec.step,
                host_ns=start)
            self.annotation.__enter__()
        rec.starts[self.id] = start
        return self

    def count(self, **counts) -> None:
        """Add to this span's counts."""
        mine = self.rec.counts[self.id]
        for k, v in counts.items():
            mine[k] = mine.get(k, 0) + v

    def __exit__(self, *exc):
        rec = self.rec
        rec.ends[self.id] = time.perf_counter_ns()
        if self.annotation is not None:
            if rec.counts[self.id]:
                self.annotation.set_metadata(**rec.counts[self.id])
            self.annotation.__exit__(*exc)
        rec._open.pop()
        if not rec._open:
            _LIVE.remove(rec)
        return False


class SpanRecorder:
    """Spans, counts and events of one or more ``run`` calls, in memory.

    Each span and event carries the serving loop's step at which it began
    (-1 before the first :meth:`step_span`).
    """

    on = True

    def __init__(self) -> None:
        self.step = -1
        self.names: list[str] = []
        self.starts: list[int] = []
        self.ends: list[int] = []
        self.parents: list[int] = []
        self.reqs: list[tuple[int, ...]] = []
        self.steps: list[int] = []
        self.counts: list[dict] = []
        # (name, req, ns, step)
        self.events: list[tuple[str, int, int, int]] = []
        self._open: list[int] = []
        _listen()

    def span(self, name: str, *, req=(), leaf: bool = True,
             **counts) -> _Span:
        """A context manager recording one span named ``name``.

        ``req`` is a request id or a tuple of them; ``leaf`` says whether the
        span opens none inside it (and so is mirrored onto the profiler);
        ``counts`` start its counts.
        """
        sid = len(self.names)
        self.names.append(name)
        self.starts.append(0)
        self.ends.append(0)
        self.parents.append(self._open[-1] if self._open else -1)
        self.reqs.append(req if isinstance(req, tuple) else (req,))
        self.steps.append(self.step)
        self.counts.append(dict(counts))
        return _Span(self, sid, leaf)

    def step_span(self, step: int) -> _Span:
        """The enclosing span ``serve.step`` of the loop's step ``step``."""
        self.step = step
        return self.span("serve.step", leaf=False)

    def event(self, name: str, req: int) -> None:
        """Stamp a per-request event now."""
        self.events.append((name, req, time.perf_counter_ns(), self.step))

    def _credit(self, counter: str, n: float) -> None:
        if self._open:
            mine = self.counts[self._open[-1]]
            mine[counter] = mine.get(counter, 0) + n

    def dump(self) -> dict:
        """Every closed span and every event, as plain JSON-ready values.

        ``spans``: one dict per span (``id``, ``name``, ``start_ns``,
        ``end_ns``, ``parent`` (-1 for a root), ``req``, ``step``,
        ``counts``); ``events``: one dict per event (``name``, ``req``,
        ``t_ns``, ``step``).  Times are ``time.perf_counter_ns`` readings.
        """
        spans = [{"id": i, "name": n, "start_ns": s, "end_ns": e,
                  "parent": p, "req": list(r), "step": st, "counts": dict(c)}
                 for i, (n, s, e, p, r, st, c) in enumerate(zip(
                     self.names, self.starts, self.ends, self.parents,
                     self.reqs, self.steps, self.counts)) if e]
        events = [{"name": n, "req": r, "t_ns": t, "step": st}
                  for n, r, t, st in self.events]
        return {"clock": "perf_counter_ns", "spans": spans, "events": events}


def follow_profiler(rec):
    """The recorder a loop given no recorder uses for its next step.

    While a profiler records this process, ``rec`` if it is already a live
    recorder, else a new :class:`SpanRecorder`: the loop's phases then land
    in the trace.  Otherwise :data:`NULL`.
    """
    if jax.profiler.TraceAnnotation.is_enabled():
        return rec if rec.on else SpanRecorder()
    return NULL


# -- the one process-wide listener --------------------------------------------

#: recorders with a span open, innermost last; the listener credits the last
_LIVE: list[SpanRecorder] = []
_LISTENING = False
_LISTEN_LOCK = threading.Lock()
_gc_started = 0.0


def _on_event(event: str, **_) -> None:
    if _LIVE and event == CACHE_HIT_EVENT:
        _LIVE[-1]._credit("cache_hits", 1)


def _on_duration(event: str, duration: float, **_) -> None:
    if not _LIVE:
        return
    if event == JIT_TRACE_EVENT:
        _LIVE[-1]._credit("jit_traces", 1)
    elif event == COMPILE_EVENT:
        _LIVE[-1]._credit("compiles", 1)


def _on_gc(phase: str, info: dict) -> None:
    global _gc_started
    if not _LIVE:
        return
    if phase == "start":
        _gc_started = time.perf_counter()
    elif _gc_started:
        _LIVE[-1]._credit("gc", 1)
        _LIVE[-1]._credit("gc_ms", 1e3 * (time.perf_counter() - _gc_started))
        _gc_started = 0.0


def _listen() -> None:
    global _LISTENING
    with _LISTEN_LOCK:
        if _LISTENING:
            return
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        gc.callbacks.append(_on_gc)
        _LISTENING = True
