"""Median over the requests due in the window of the time from each one's
due time to its first token on the host (never: infinite)."""

from harness import timeline


def read(r):
    return timeline.percentile(
        timeline.ttfts(r.due_at(), r.served.scheduler.first_at), 50)
