"""Puts the benchmark's harness and the program's ``src`` on the path and
keeps JAX on the CPU, for the harness tests."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def smoke_cell(name: str, bench_root=ROOT, **over):
    """A cell of the benchmark under ``bench_root`` with its model cut to
    the registry's smoke sizes, for driving the harness on the CPU."""
    import dataclasses

    from harness import spec
    from repro.configs import get_smoke_config

    cell = spec.load_cell(name, bench_root)
    cfg = get_smoke_config(cell.cfg.arch_id).replace(
        param_dtype="bfloat16", compute_dtype="bfloat16")
    return dataclasses.replace(cell, cfg=cfg,
                               dims=cell.arch.Dims.from_model(cfg), **over)
