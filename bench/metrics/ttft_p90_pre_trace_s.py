"""Scheduler: 90th percentile of due time to first token on the host, over
the requests due before the profiler started (never: infinite).  Stopping
the profiler holds the host loop for seconds, so requests due after it
started wait on the trace, not on the engine."""

from harness import timeline


def read(r):
    due = r.due_before_trace()
    return timeline.percentile(
        timeline.ttfts(due, r.served.scheduler.first_at), 90) if due else None
