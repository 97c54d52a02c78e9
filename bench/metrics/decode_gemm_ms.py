"""Model step, decode: device time per call of the jitted decode step
(``_decode_fn``) in ops whose HLO ``op_name`` lies under a weight GEMM's
site scope (``wq wk wv wo``, ``w_gate w_up w_down``, ``lm_head``)."""

from harness import program


def read(r):
    pt = program.of(r)
    if pt is None:
        return None
    return program.scope_ms(r.trace.ops_by_device[0],
                            r.trace.programs("_decode_fn"),
                            pt.hlo("_decode_fn"), program.is_gemm)
