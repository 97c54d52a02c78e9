"""Continuous-batching serving on the unary backend/plan/grid stack.

The request-serving loop the ROADMAP's north star hangs off: a paged KV
cache (``paged_kv``) read through the gather-based decode path in
``kernels.paged_attention``, a continuous-batching scheduler with
page-reservation admission control (``scheduler``), a seeded synthetic
traffic generator (``traffic``), Eq.-1 energy-per-token accounting
(``energy``), and the engine that jits one ragged decode step for the whole
batch under ``use_backend(...)``/``use_plan(...)`` (``engine``).

See ``docs/SERVING.md`` for the scheduler states, page-table layout,
admission rules and accounting; ``tests/test_serving.py`` pins the
allocator invariants, the paged-vs-contiguous bit-exactness, and the
seed-determinism of the whole loop.
"""

from repro.serving.engine import (FUSED_LOGIT_TOL, PREFILL_LOGIT_TOL,
                                  PagedProbe, ServingEngine, ServingReport,
                                  fused_vs_gather_probe,
                                  paged_vs_contiguous_probe)
from repro.serving.paged_kv import OutOfPages, PageAllocator, PagedKVCache
from repro.serving.scheduler import (ContinuousBatchingScheduler, Request,
                                     RequestState, StaticBatchingScheduler,
                                     make_scheduler)
from repro.serving.traffic import TrafficConfig, TrafficRequest, generate_trace

__all__ = [
    "ServingEngine", "ServingReport", "PagedProbe",
    "paged_vs_contiguous_probe", "fused_vs_gather_probe", "FUSED_LOGIT_TOL",
    "PREFILL_LOGIT_TOL",
    "OutOfPages", "PageAllocator", "PagedKVCache",
    "ContinuousBatchingScheduler", "StaticBatchingScheduler",
    "Request", "RequestState", "make_scheduler",
    "TrafficConfig", "TrafficRequest", "generate_trace",
]
