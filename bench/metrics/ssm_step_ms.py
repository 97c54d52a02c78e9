"""Model step, decode: device time per call of a layer pattern's jitted
decode step (``_hybrid_decode_fn``) in ops whose HLO ``op_name`` lies under
the ``ssm_step`` scope: the Mamba2 layers' single-token recurrence between
the projections (conv and tail shift, state update, readout, the state's
read and write-back)."""

from harness import program

PROGRAM = "_hybrid_decode_fn"


def read(r):
    pt = program.of(r)
    if pt is None:
        return None
    return program.scope_ms(r.trace.ops_by_device[0],
                            r.trace.programs(PROGRAM), pt.hlo(PROGRAM),
                            program.in_scope("ssm_step"))
