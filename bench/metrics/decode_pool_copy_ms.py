"""Model step, decode: device time per call of the jitted decode step
(``_decode_fn``) in ops under its layer scan (``decode_layers``) and under
no site scope: the scan's slicing of each layer's inputs (the KV pool
slices) and stacking of its outputs, outside ``kv_write`` and
``page_walk``."""

from harness import program


def read(r):
    pt = program.of(r)
    if pt is None:
        return None
    return program.scope_ms(r.trace.ops_by_device[0],
                            r.trace.programs("_decode_fn"),
                            pt.hlo("_decode_fn"), program.is_pool_copy)
