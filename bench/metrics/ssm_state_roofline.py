"""Kernels: the Mamba2 single-token recurrence's share of its roofline.
Its work is each decoded row's float32 SSM state and conv tails, read and
written, in every Mamba2 layer, and the recurrence's operations (counts in
the cell's architecture module); the least time for that work on the chip,
over the device time per decode call of the ops under the ``ssm_step``
scope (``ssm_step_ms``)."""

from harness import counts, program

PROGRAM = "_hybrid_decode_fn"


def read(r):
    arch = r.cell.arch
    pt = program.of(r)
    steps = r.profiled_steps()
    if pt is None or not steps or not hasattr(arch, "ssm_state_bytes"):
        return None
    ms = program.scope_ms(r.trace.ops_by_device[0], r.trace.programs(PROGRAM),
                          pt.hlo(PROGRAM), program.in_scope("ssm_step"))
    if ms is None:
        return None
    rows = sum(n for _, n, _ in steps) / len(steps)
    least = counts.roofline_seconds(arch.ssm_step_flops(r.cell.dims, rows),
                                    arch.ssm_state_bytes(r.cell.dims, rows),
                                    r.peak)
    return 100.0 * least / (1e-3 * ms)
