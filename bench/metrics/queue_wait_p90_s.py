"""Scheduler: 90th percentile of due time to admission, on the open-loop
scheduler's clock, over the requests due before the profiler started
(stopping it holds the host loop for seconds)."""

from harness import timeline


def read(r):
    due = r.due_before_trace()
    return timeline.percentile(
        timeline.queue_waits(due, r.served.scheduler.picked_at), 90) \
        if due else None
