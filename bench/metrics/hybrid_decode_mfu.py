"""Whole decode step of a layer pattern: the model's operations in the
traced decode steps (2 x matmul weights x rows decoded, the Mamba2
recurrences, attention over each row's context in the attention layers;
counts in the cell's architecture module) over the decode program's
(``_hybrid_decode_fn``) device time, as a share of the chip's bf16
peak."""

PROGRAM = "_hybrid_decode_fn"


def read(r):
    arch = r.cell.arch
    calls = r.trace.programs(PROGRAM) if r.trace else []
    steps = r.profiled_steps()
    if not calls or not steps or not hasattr(arch, "decode_step_flops"):
        return None
    per_step = sum(arch.decode_step_flops(r.cell.dims, rows, ctx)
                   for _, rows, ctx in steps) / len(steps)
    busy = sum(c.seconds for c in calls)
    return 100.0 * len(calls) * per_step / busy / r.peak["bf16_flop_s"]
