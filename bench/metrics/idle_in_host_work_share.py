"""Host loop: share of the traced stretch in which no operation ran on the
chip while the host was inside one of the program's own host-work spans:
``admit.*`` and ``prefill.*`` (the device wait ``admit.first_token``
aside), ``kv.write_prefill``, ``decode.dispatch``, ``decode.bookkeep``."""

from harness import program, trace as trace_lib

HOST_WORK = ("kv.write_prefill", "decode.dispatch", "decode.bookkeep")


def _host_work(name: str) -> bool:
    return name in HOST_WORK or (name.startswith(("admit.", "prefill."))
                                 and name not in program.DEVICE_WAITS)


def read(r):
    pt = program.of(r)
    if pt is None or not pt.spans:
        return None
    lo, hi = pt.stretch(r.served.profile)
    idle = trace_lib.gaps(r.trace.ops_by_device[0], lo, hi)
    work = [(s.start_ns, s.end_ns) for s in program.inside(pt.spans, lo, hi)
            if _host_work(s.name)]
    return 100.0 * program.overlap_ns(idle, work) / (hi - lo)
