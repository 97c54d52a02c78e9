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


def smoke_cell(name: str, **over):
    """A cell of the benchmark with its model cut to the registry's smoke
    sizes, for driving the harness on the CPU."""
    import dataclasses

    from harness import spec
    from repro.configs import get_smoke_config

    cell = spec.load_cell(name)
    reg = spec.read_json(BENCH / "configs" / f"{cell.config_name}.json")
    cfg = get_smoke_config(reg["registry_id"]).replace(
        param_dtype="bfloat16", compute_dtype="bfloat16")
    dims = spec.Dims(layers=cfg.num_layers, d_model=cfg.d_model,
                     heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                     head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
                     vocab=cfg.vocab_size, rope_theta=cfg.rope_theta,
                     rms_eps=cfg.rms_eps)
    return dataclasses.replace(cell, cfg=cfg, dims=dims, **over)
