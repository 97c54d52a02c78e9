"""Host loop: median over the loop's steps in the traced stretch of each
step's length (its first span's start to the next step's) less the time
its spans waited on the device (``decode.read_tokens``,
``admit.first_token``): the host's own time per step."""

import statistics

from harness import program


def read(r):
    pt = program.of(r)
    if pt is None or not pt.spans:
        return None
    lo, hi = pt.stretch(r.served.profile)
    own = program.step_self_ns(program.inside(pt.spans, lo, hi))
    return 1e-6 * statistics.median(own) if own else None
