"""Offer one cell's traffic at several rates, to find its knee.

    python bench/sweep.py --workload <name> --seed <n> --seconds <T> \
        --rates 1.0,1.5,2.0

One process sets the cell up once (warming every prompt length any of the
rates offers) and then serves one window per rate, in the order given.
Per rate it prints one JSON line: tokens/s, the tails, and the backlog,
the requests due in the window but not admitted when it closed.  The knee
is the highest rate at which that backlog does not grow over the window.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, enable_cache, find_chips  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from harness import serve, spec, timeline, traffic
    cell = spec.load_cell(args.workload, ROOT)
    find_chips(cell.chips)
    enable_cache()
    rates = [float(r) for r in args.rates.split(",")]
    offers = {r: traffic.arrivals(cell.traffic, r, args.seconds, args.seed)
              for r in rates}
    times = {}
    engine, _ = serve.setup(cell, args.seed,
                            [a for arr in offers.values() for a in arr], times)
    print(json.dumps({"setup": times}), flush=True)
    for rate in rates:
        s = serve.window(cell, engine, offers[rate], args.seconds)
        sch, close = s.scheduler, s.start + s.seconds
        due = {a.req_id: s.start + a.due_s for a in s.arrivals}
        half = len(s.arrivals) // 2
        waits = timeline.queue_waits(due, sch.picked_at)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(s.arrivals),
            "output_tok_s": timeline.output_tok_s(sch.token_at, s.start,
                                                  s.seconds),
            "ttft_p50_s": timeline.percentile(
                timeline.ttfts(due, sch.first_at), 50),
            "ttft_p90_s": timeline.percentile(
                timeline.ttfts(due, sch.first_at), 90),
            "itl_p95_s": timeline.percentile(
                timeline.token_gaps(sch.token_at, s.start, s.seconds), 95),
            "queue_wait_p90_first_half_s": timeline.percentile(
                waits[:half], 90),
            "queue_wait_p90_second_half_s": timeline.percentile(
                waits[half:], 90),
            "backlog_at_close": sum(1 for r, t in sch.picked_at.items()
                                    if t > close),
            "occupancy": timeline.occupancy(
                [x for x in sch.decode_steps if x[0] < close],
                cell.max_batch),
            "drain_s": time.perf_counter() - close,
            "compiles_in_window": s.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
