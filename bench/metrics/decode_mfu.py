"""Whole decode step: the model's operations in the traced decode steps
(2 x matmul weights x rows decoded, plus attention over each row's
context) over the decode program's device time, as a share of the chip's
bf16 peak.  The model's work, not the emulation's: a unary backend counts
the same operations."""

from harness import counts


def read(r):
    calls = r.trace.programs("_decode_fn") if r.trace else []
    steps = r.profiled_steps()
    if not calls or not steps:
        return None
    per_step = sum(counts.decode_step_flops(r.cell.dims, rows, ctx)
                   for _, rows, ctx in steps) / len(steps)
    busy = sum(c.seconds for c in calls)
    return 100.0 * len(calls) * per_step / busy / r.peak["bf16_flop_s"]
