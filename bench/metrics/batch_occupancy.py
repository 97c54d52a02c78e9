"""Scheduler: mean rows decoded per decode step in the window, as a share
of the engine's slots (the open-loop scheduler's counts)."""

from harness import timeline


def read(r):
    s = r.served
    steps = [st for st in s.scheduler.decode_steps
             if s.start <= st[0] < s.start + s.seconds]
    occ = timeline.occupancy(steps, r.cell.max_batch)
    return None if occ is None else 100.0 * occ
