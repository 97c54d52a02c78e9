"""Device: share of the traced stretch in which no operation ran on the
chip (1 - union of device op intervals / stretch)."""


def read(r):
    if not r.trace or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
