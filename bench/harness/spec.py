"""A benchmark cell, assembled from the data files its name points at.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
harness finds everything else by those names, so a later cell is added by
adding files, never by editing one:

* ``configs/<config>.json`` — the published sizes as run, the
  architecture (``archs/<arch>.py``), the registry id they map onto, the
  execution mode and the engine's sizes;
* ``modes/<mode>.json`` — how the weight GEMMs execute;
* ``traffic/<mix>.json`` — the parameters the one traffic generator reads;
* ``cells/<workload>.json`` — the cell's offered rate and the limit of each
  number its correctness check compares.
"""

from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

_ARCH_NAME = re.compile(r"[A-Za-z][A-Za-z0-9_]*")


def load_arch(name, bench_root: Path = ROOT):
    """The architecture module ``bench/archs/<name>.py`` under
    ``bench_root``, imported by its path (``archs/__init__.py`` says what it
    provides).  A missing or unknown name raises."""
    if not isinstance(name, str) or not _ARCH_NAME.fullmatch(name):
        raise ValueError(f"no architecture named {name!r}: a configuration "
                         "file names one under \"arch\"")
    path = (Path(bench_root) / "bench" / "archs" / f"{name}.py").resolve()
    if not path.is_file():
        raise ValueError(f"unknown architecture {name!r}: no {path}")
    # this checkout's modules keep their package name, so ``archs.<name>``
    # imported by another module is the same module object
    key = (f"archs.{name}" if path.parent == BENCH_DIR / "archs" else
           f"bench_arch_{hashlib.sha1(str(path).encode()).hexdigest()[:12]}")
    mod = sys.modules.get(key)
    if mod is None:
        found = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(found)
        sys.modules[key] = mod
        try:
            found.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return mod


def _field(cfg, path: str):
    for part in path.split("."):
        cfg = getattr(cfg, part)
    return cfg


def _with_field(cfg, path: str, value):
    """``cfg`` with the (dotted, nested) field ``path`` set to ``value``."""
    head, _, rest = path.partition(".")
    if rest:
        value = _with_field(getattr(cfg, head), rest, value)
    return dataclasses.replace(cfg, **{head: value})


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything one run of a workload needs, from its files."""
    name: str
    config_name: str
    traffic_name: str
    chips: int
    arch: object           # the module archs/<arch>.py the config names
    cfg: object            # repro.models.config.ModelConfig the engine runs
    dims: object           # the same sizes, for the reference (arch.Dims)
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


def model_config(conf: dict, bench_root: Path = ROOT):
    """The registry's ModelConfig for ``conf``, checked against its sizes.

    The registry holds the published model.  Each key of the
    architecture's ``CONFIG_KEYS`` is compared with the file's ``config``
    as run, except a key the file lists under ``reduced``: that one is
    compared with its ``published`` value, then set to the value run.
    """
    from repro.configs import get_config
    arch = load_arch(conf.get("arch"), bench_root)
    as_run, published = conf["config"], conf.get("published", {})
    cut = set(conf.get("reduced", {}))
    missing = sorted(cut - set(published))
    if missing:
        raise ValueError(f"{conf['registry_id']}: reduced {missing} have no "
                         "published value to check the registry with")
    cfg = get_config(conf["registry_id"]).replace(
        param_dtype=as_run["torch_dtype"], compute_dtype="bfloat16")
    for key, field in arch.CONFIG_KEYS.items():
        have = _field(cfg, field)
        src, want = (("published", published[key]) if key in cut
                     else ("config file", as_run[key]))
        if have != want:
            raise ValueError(f"{conf['registry_id']}: registry {field}="
                             f"{have!r}, {src} {key}={want!r}")
    for key in sorted(cut & set(arch.CONFIG_KEYS)):
        cfg = _with_field(cfg, arch.CONFIG_KEYS[key], as_run[key])
    arch.check(cfg, as_run)
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
    arch = load_arch(conf.get("arch"), bench_root)
    cfg = model_config(conf, bench_root)
    dims = arch.Dims.from_config(conf["config"])
    if arch.Dims.from_model(cfg) != dims:
        raise ValueError(f"{w['config']}: the sizes run differ from the "
                         "registry's with the cuts applied")
    return Cell(name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]), arch=arch,
                cfg=cfg, dims=dims, mode=mode, traffic=traffic,
                rate_per_s=float(cell["rate_per_s"]),
                limits=dict(cell["limits"]), max_batch=eng["max_batch"],
                page_size=eng["page_size"], max_seq_len=eng["max_seq_len"])
