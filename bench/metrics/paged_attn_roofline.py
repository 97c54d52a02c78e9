"""Kernels: the fused Pallas page walk's share of its roofline.  Its work
is the K and V of every decoded row's context at bf16 and the scores and
weighted values over it; the least time for that work on the chip, over
the summed device time of the kernel's calls in the traced stretch."""

from harness import counts

KERNEL = "_fused_decode_pallas"


def read(r):
    calls = r.trace.programs("_decode_fn") if r.trace else []
    kernel = r.trace.ops(KERNEL) if r.trace else []
    steps = r.profiled_steps()
    if not calls or not kernel or not steps:
        return None
    ctx = sum(c for _, _, c in steps) / len(steps) * len(calls)
    least = counts.roofline_seconds(counts.attention_flops(r.cell.dims, ctx),
                                    counts.kv_bytes(r.cell.dims, ctx), r.peak)
    return 100.0 * least / sum(k.seconds for k in kernel)
