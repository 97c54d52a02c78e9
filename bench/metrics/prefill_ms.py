"""Model step, prefill: device time per call of the jitted admission
prefill program (``prefill_fn``) in the traced stretch."""


def read(r):
    calls = r.trace.programs("prefill_fn") if r.trace else []
    return 1e3 * sum(c.seconds for c in calls) / len(calls) if calls else None
