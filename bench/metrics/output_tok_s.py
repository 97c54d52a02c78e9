"""Output tokens that reached the host inside the window, per second."""

from harness import timeline


def read(r):
    s = r.served
    return timeline.output_tok_s(s.scheduler.token_at, s.start, s.seconds)
