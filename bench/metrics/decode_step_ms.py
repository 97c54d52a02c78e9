"""Model step, decode: device time per call of the jitted decode step
(``_decode_fn``) in the traced stretch."""


def read(r):
    calls = r.trace.programs("_decode_fn") if r.trace else []
    return 1e3 * sum(c.seconds for c in calls) / len(calls) if calls else None
