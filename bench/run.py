"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <T> --trace <0|1>

The cell's configuration, mode, traffic mix, rate and limits come from
``BENCHMARK.json`` and the data files its names point at (``harness/spec.py``).
One run: find the chips or exit non-zero; make the weights from the seed;
build the engine; warm up every shape the cell's traffic reaches; offer the
traffic for ``--seconds`` seconds through ``ServingEngine.run``; let the
queue drain (not counted); read the peak memory; compare a sample of what
was served with the plain float32 reference; print one JSON line.  With
``--trace 1`` a few seconds of steady serving are profiled and the line
carries the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]
# the persistent compile cache lives at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache" / "bench"
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips: int) -> None:
    """Exit non-zero unless JAX sees at least ``chips`` TPU devices."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"need {chips} TPU chip(s), JAX found {len(devs)} "
            f"{devs[0].platform} device(s): no result")
        sys.exit(3)


def enable_cache() -> None:
    """JAX's persistent compile cache at the checkout's fixed path, holding
    every program, the small eager ones of the admission path too."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    args = parse(argv)
    from harness import spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.load_cell(args.workload, ROOT)
    find_chips(cell.chips)
    enable_cache()
    from harness import report
    result = report.run(bench, cell, args.seed, args.seconds,
                        bool(args.trace), t_proc=T_PROC,
                        trace_root=ROOT / "chiprun_out" / "traces")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
