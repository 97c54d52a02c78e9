"""One run of one cell: build, warm up, serve the window, drain, check.

The timed path is the program's own ``ServingEngine.run`` with continuous
batching, driven by the benchmark's open-loop scheduler.  The benchmark
brings the weights (``weights.make`` of the architecture's ``shapes``) and
the prompts (``traffic.prompt_tokens``, put in place of the engine's own
prompt generator), so the engine receives only generated inputs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import jax
import numpy as np

from harness import traffic, weights
from harness.scheduler import OpenLoopScheduler

#: seconds of steady serving the profiler records in a ``--trace 1`` run,
#: starting this share of the window after it opens
TRACE_SECONDS = 6.0
TRACE_AT = 0.4


@dataclasses.dataclass
class Served:
    """What one window left behind for the metrics and the check."""
    arrivals: list
    scheduler: OpenLoopScheduler
    start: float
    seconds: float
    tokens: dict          # req_id -> served token ids
    finished: set
    compiles_in_window: int
    setup: dict           # seconds of each set-up phase
    profile: tuple | None = None   # (host start, host stop, trace dir)


class CompileCounter:
    """Host times of every backend compile in this process."""

    def __init__(self):
        self.times: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(1 for t in self.times if a <= t <= b)


COMPILES = CompileCounter()


def build_engine(cell, params, seed: int):
    from repro.serving.engine import ServingEngine
    mode = cell.mode
    kw = {}
    if mode.get("backend"):
        kw = dict(backend=mode["backend"], bits=mode["bits"],
                  packed=mode.get("packed", False))
    engine = ServingEngine(cell.cfg, params, max_batch=cell.max_batch,
                           page_size=cell.page_size,
                           max_seq_len=cell.max_seq_len, **kw)
    vocab = cell.dims.vocab
    engine.prompt_tokens = lambda req: traffic.prompt_tokens(
        seed, req.req_id, req.prompt_len, vocab)
    return engine


def mode_scope(cell):
    """The trace-time context the mode's GEMMs run under."""
    scaling = cell.mode.get("activation_scaling")
    if scaling is None:
        return contextlib.nullcontext()
    from repro.models.common import activation_scaling
    return activation_scaling(scaling)


def warm_up(cell, engine, arrivals, bucket) -> None:
    """Compile every shape the window will reach, before it opens.

    One short pass through ``run`` admits a prompt of every padded prefill
    width and every partial last page, and decodes.  The admission path
    also slices each prompt's rows out of the padded prefill, and each page
    out of those rows, at the prompt's own length: a program per distinct
    length.  So every other length the window offers is then prefilled
    through the engine's admission prefill, a full batch at a time, and
    written into a scratch cache of one request's pages.
    """
    from repro.serving.paged_kv import PagedKVCache
    warm = traffic.warmup_arrivals(cell.traffic, cell.page_size, bucket)
    todo = sorted({a.prompt_len for a in arrivals}
                  - {a.prompt_len for a in warm})
    with mode_scope(cell):
        sched = _scheduler(cell, warm)
        sched.open(time.perf_counter())
        engine.run(_requests(warm), sched)
        cfg = cell.cfg
        scratch = PagedKVCache(
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim,
            num_pages=1 + -(-cell.max_seq_len // cell.page_size),
            page_size=cell.page_size, max_seq_len=cell.max_seq_len)
        by_width: dict[int, list[int]] = {}
        for n in todo:
            by_width.setdefault(bucket(n), []).append(n)
        with jax.set_mesh(engine._mesh), engine._scope():
            for lens in by_width.values():
                for i in range(0, len(lens), cell.max_batch):
                    chunk = lens[i:i + cell.max_batch]
                    rows = engine._prefill_rows([np.zeros(n, np.int32)
                                                 for n in chunk])
                    for _, k_rows, v_rows in rows:
                        scratch.allocate(0, k_rows.shape[1])
                        scratch.write_prefill(0, k_rows, v_rows)
                        scratch.free_request(0)
        jax.block_until_ready(scratch.k_pool)


def _requests(arrivals):
    from repro.serving.traffic import TrafficRequest
    # every request is handed over at step 0: the scheduler gates on time
    return tuple(TrafficRequest(a.req_id, 0, a.prompt_len, a.output_len)
                 for a in arrivals)


def _scheduler(cell, arrivals):
    return OpenLoopScheduler(cell.max_batch,
                             {a.req_id: a.due_s for a in arrivals},
                             {a.req_id: a.prompt_len for a in arrivals})


def setup(cell, seed: int, arrivals, setup_times: dict):
    """Weights, engine and warm-up for a window offering ``arrivals``."""
    from repro.serving.engine import _bucket
    t = time.perf_counter()
    params = weights.make(cell.arch.shapes(cell.dims), seed)
    jax.block_until_ready(params)
    setup_times["init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine = build_engine(cell, params, seed)
    jax.block_until_ready(engine._exec_params)
    setup_times["engine_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_up(cell, engine, arrivals, _bucket)
    setup_times["warmup_s"] = time.perf_counter() - t
    return engine, params


def window(cell, engine, arrivals, seconds: float, *,
           trace_dir: str | None = None, setup_times=None,
           t_proc: float | None = None) -> Served:
    """Offer ``arrivals`` for ``seconds`` through ``engine.run``, drain."""
    sched = _scheduler(cell, arrivals)
    profile = {}
    start = time.perf_counter()
    if trace_dir is not None:
        def begin():
            jax.profiler.start_trace(
                trace_dir, profiler_options=_profile_options())
            profile["start"] = time.perf_counter()

        def end():
            profile["stop"] = time.perf_counter()
            jax.profiler.stop_trace()

        t0 = start + TRACE_AT * seconds
        sched.at(t0, begin)
        sched.at(t0 + TRACE_SECONDS, end)
    sched.open(start)
    setup_times = {} if setup_times is None else setup_times
    if t_proc is not None:
        setup_times["setup_s"] = start - t_proc
    with mode_scope(cell):
        report = engine.run(_requests(arrivals), sched)
    sched.release()
    if "start" in profile and "stop" not in profile:
        profile["stop"] = time.perf_counter()
        jax.profiler.stop_trace()
    want = {a.req_id: a.output_len for a in arrivals}
    return Served(
        arrivals=arrivals, scheduler=sched, start=start, seconds=seconds,
        tokens={k: list(v) for k, v in report.request_tokens.items()},
        finished={r for r, toks in report.request_tokens.items()
                  if len(toks) == want[r]},
        compiles_in_window=COMPILES.between(start, start + seconds),
        setup=setup_times,
        profile=((profile["start"], profile["stop"], trace_dir)
                 if "start" in profile else None))


def serve(cell, seed: int, seconds: float, *, t_proc: float,
          trace_dir: str | None = None) -> tuple[Served, dict]:
    """Set up, serve one window, drain.  Returns what was served and the
    weights (which the reference reads once the engine is gone)."""
    arrivals = traffic.arrivals(cell.traffic, cell.rate_per_s, seconds, seed)
    times = {}
    engine, params = setup(cell, seed, arrivals, times)
    served = window(cell, engine, arrivals, seconds, trace_dir=trace_dir,
                    setup_times=times, t_proc=t_proc)
    del engine
    gc.collect()  # the engine's jitted step refers back to it: a cycle
    return served, params


def _profile_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def peak_bytes() -> int:
    """Peak bytes in use on the fullest device so far."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak_bytes()}

