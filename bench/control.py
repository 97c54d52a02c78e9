"""The program's readings and its control's, over many seeds, in one process.

    python bench/control.py --workload <name> --seconds <T> --seeds 1,2,3

For each seed: make the weights, build and warm the engine, serve a window
of the cell's own traffic at its own rate, drain, then read on the same
sample of served requests both the program's widest logit gap and the
control's (the float32 reference with float8 weights put in the program's
place, each position's first choice read by the float32 reference).
Prints one JSON line per seed.  The cell's limit is set between the
largest program reading and the smallest control reading.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, enable_cache, find_chips  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from harness import correct, serve, spec
    cell = spec.load_cell(args.workload, ROOT)
    find_chips(cell.chips)
    enable_cache()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        served, params = serve.serve(cell, seed, args.seconds, t_proc=t0)
        read = correct.readings(cell, params, seed, served, control=True)
        print(json.dumps({"seed": seed, **read,
                          "unfinished": len(served.arrivals)
                          - len(served.finished),
                          "seconds": time.perf_counter() - t0}), flush=True)
        del served, params
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
