"""A benchmark cell, assembled from the data files its name points at.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
harness finds everything else by those names, so a later cell is added by
adding files, never by editing one:

* ``configs/<config>.json`` — the published sizes as run, the registry id
  they map onto, the execution mode and the engine's sizes;
* ``modes/<mode>.json`` — how the weight GEMMs execute;
* ``traffic/<mix>.json`` — the parameters the one traffic generator reads;
* ``cells/<workload>.json`` — the cell's offered rate and the limit of each
  number its correctness check compares.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

# published config.json key -> (ModelConfig field, how the registry holds it)
_CONFIG_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
}
_ACTIVATIONS = {"silu": "swiglu"}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The model's sizes, as the plain reference reads them."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        if c.get("hidden_act") != "silu" or c.get("tie_word_embeddings"):
            raise ValueError("the reference covers untied SwiGLU decoders")
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   rms_eps=float(c["rms_norm_eps"]))


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything one run of a workload needs, from its files."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    cfg: object            # repro.models.config.ModelConfig the engine runs
    dims: Dims             # the same sizes, for the reference
    mode: dict
    traffic: dict
    rate_per_s: float
    limits: dict
    max_batch: int
    page_size: int
    max_seq_len: int


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(bench_root: Path = ROOT) -> dict:
    return read_json(Path(bench_root) / "BENCHMARK.json")


def model_config(conf: dict):
    """The registry's ModelConfig for ``conf``, checked against its sizes."""
    from repro.configs import get_config
    cfg = get_config(conf["registry_id"]).replace(
        param_dtype=conf["config"]["torch_dtype"], compute_dtype="bfloat16")
    published = conf["config"]
    for key, field in _CONFIG_KEYS.items():
        have = getattr(cfg, field)
        if have != published[key]:
            raise ValueError(f"{conf['registry_id']}: registry {field}="
                             f"{have!r}, config file {key}={published[key]!r}")
    if cfg.activation != _ACTIVATIONS[published["hidden_act"]] \
            or cfg.resolved_head_dim * cfg.num_heads != cfg.d_model:
        raise ValueError(f"{conf['registry_id']}: activation or head width "
                         "differs from the config file")
    return cfg


def load_cell(name: str, bench_root: Path = ROOT) -> Cell:
    """The workload ``name`` of ``BENCHMARK.json``, with its files read."""
    bench_root = Path(bench_root)
    spec = load_benchmark(bench_root)
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json") from None
    base = bench_root / "bench"
    conf = read_json(base / "configs" / f"{w['config']}.json")
    traffic = read_json(base / "traffic" / f"{w['traffic']}.json")
    cell = read_json(base / "cells" / f"{name}.json")
    mode = read_json(base / "modes" / f"{conf['mode']}.json")
    eng = conf["engine"]
    if eng["max_seq_len"] != conf["config"]["max_position_embeddings"]:
        raise ValueError(f"{w['config']}: engine max_seq_len differs from "
                         "max_position_embeddings")
    need = traffic["prompt"]["max"] + traffic["output"]["max"]
    if need > eng["max_seq_len"]:
        raise ValueError(f"{name}: traffic needs {need} positions, the "
                         f"configuration serves {eng['max_seq_len']}")
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                cfg=model_config(conf), dims=Dims.from_config(conf["config"]),
                mode=mode, traffic=traffic,
                rate_per_s=float(cell["rate_per_s"]),
                limits=dict(cell["limits"]), max_batch=eng["max_batch"],
                page_size=eng["page_size"], max_seq_len=eng["max_seq_len"])
