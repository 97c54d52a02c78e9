"""Admission KV write: device time of the programs dispatched inside the
program's ``kv.write_prefill`` spans (a request's page writes), per
admitted request whose writes ran in the traced stretch."""

from harness import program


def read(r):
    pt = program.of(r)
    if pt is None or not pt.spans:
        return None
    lo, hi = pt.stretch(r.served.profile)
    writes = [s for s in program.inside(pt.spans, lo, hi)
              if s.name == "kv.write_prefill"]
    runs = [rs for rs in program.runs_in(pt.pairs, writes).values() if rs]
    if not runs:
        return None
    return 1e3 * sum(run.seconds for rs in runs for run in rs) / len(runs)
