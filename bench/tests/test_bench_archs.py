"""Architectures as files: the dense GQA module pinned, and room for another.

The pinned digests were taken with the harness as it stood before the
architecture moved into ``archs/dense_gqa.py`` (the weight tree built in
``harness/weights.py``, the forward pass in ``harness/reference.py``), at
the registry's smoke sizes on the CPU: the move changed no weight byte and
no logit bit, float32 or fp8.  XLA's optimisation passes decide how a
float32 product is fused and so its last bits, and the repository's
``tests/conftest.py`` turns most of them off for the process, so the
readings are taken in a process of their own with XLA's defaults, as the
digests were:

    JAX_PLATFORMS=cpu python bench/tests/test_bench_archs.py
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

import _paths
from harness import correct, spec, traffic, weights

SEED = 2**31 + 11
POSITIONS = [0, 13, 31]
PINNED = {
    "internlm2-1.8b.float.code": {
        "weights": "7f5b5b1c89b5f7fbe314d70cdf00090120560ae9"
                   "f4c9d98e52b3e00fb7d9f06a",
        "float32": "2dca372506e95b35a4381a7fa7f75ec73ff00f59"
                   "3c6c45de340d967c003dbd31",
        "fp8": "707626ab26e92ec105d389818705719714297233"
               "f9e222b7dd0692077c8ca346"},
    "phi3-mini-3.8b.float.longgen": {
        "weights": "434da1d147f8b6e3143eb97bcd1d56759873b70c"
                   "7e137f24ac13bb114c923c5b",
        "float32": "19451316df70d5f4bf1784761e407c9eac19f103"
                   "6364e4b8555f8c711cc251df",
        "fp8": "3f5a6379c2b14168ea389c98ecea52d27713c7b6"
               "d27c0f3c8fbaf68a9abe4179"},
}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


def _readings() -> dict:
    """Digest of each smoke cell's weights and of its reference logits."""
    out = {}
    for name in PINNED:
        cell = _paths.smoke_cell(name)
        params = weights.make(cell.arch.shapes(cell.dims), SEED)
        tokens = traffic.prompt_tokens(SEED, 3, 32, cell.dims.vocab)
        out[name] = {"arch": cell.arch.__name__,
                     "weights": _digest(jax.tree_util.tree_leaves(params))}
        for what in ("float32", "fp8"):
            lg = cell.arch.logits(params, tokens, d=cell.dims,
                                  fp8=what == "fp8")
            out[name][what] = _digest([np.asarray(lg, np.float32)[POSITIONS]])
    return out


@pytest.fixture(scope="module")
def readings():
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_DISABLE_MOST_OPTIMIZATIONS", "XLA_FLAGS")}
    p = subprocess.run([sys.executable, __file__], capture_output=True,
                       text=True, timeout=600,
                       env={**env, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.splitlines()[-1])


@pytest.mark.parametrize("what", ["weights", "float32", "fp8"])
@pytest.mark.parametrize("name", sorted(PINNED))
def test_dense_gqa_reads_what_it_read_before_the_move(readings, name, what):
    assert readings[name]["arch"] == "archs.dense_gqa"
    assert readings[name][what] == PINNED[name][what]


# --- room: an architecture added to a copy of the benchmark by files alone

TOY = "phi3.5-moe-toy"
TOY_CELL = f"{TOY}.tiny"
TOY_CONFIG = {
    "source": "https://huggingface.co/microsoft/Phi-3.5-MoE-instruct/blob/"
              "main/config.json",
    "arch": "toy",
    "registry_id": "phi3.5-moe-42b-a6.6b",
    "mode": "float",
    "config": {
        "num_hidden_layers": 2, "hidden_size": 4096,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "intermediate_size": 6400, "num_local_experts": 4,
        "num_experts_per_tok": 2, "vocab_size": 32064,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "hidden_act": "silu",
        "tie_word_embeddings": False, "torch_dtype": "bfloat16",
        "max_position_embeddings": 64},
    "published": {"num_hidden_layers": 32, "num_local_experts": 16,
                  "max_position_embeddings": 131072},
    "reduced": {"num_hidden_layers": "depth", "num_local_experts": "held",
                "max_position_embeddings": "context"},
    "engine": {"max_batch": 2, "page_size": 4, "max_seq_len": 64},
}


def _room(tmp_path, conf=None):
    """A copy of the benchmark with the toy's files added, and nothing
    already there edited but ``BENCHMARK.json``'s lists, which gain the
    new configuration and cell."""
    root = tmp_path / "repo"
    shutil.copytree(_paths.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    b = root / "bench"
    shutil.copy(_paths.BENCH / "tests" / "toy_arch.py", b / "archs" / "toy.py")
    (b / "configs" / f"{TOY}.json").write_text(
        json.dumps(TOY_CONFIG if conf is None else conf))
    (b / "traffic" / "tiny.json").write_text(json.dumps(
        {"why": "test", "arrivals": "poisson",
         "prompt": {"dist": "uniform", "min": 6, "max": 20},
         "output": {"dist": "uniform", "min": 4, "max": 8}}))
    (b / "cells" / f"{TOY_CELL}.json").write_text(json.dumps(
        {"rate_per_s": 2.0, "limits": {"max_logit_gap": 0.15}}))
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": TOY, "source": TOY_CONFIG["source"],
                             "file": f"bench/configs/{TOY}.json",
                             "reduced": sorted(TOY_CONFIG["reduced"]),
                             "why": "test"})
    bench["workloads"].append({"name": TOY_CELL, "config": TOY,
                               "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == data for p, data in before.items())
    return root


def test_an_architecture_is_added_by_files_alone(tmp_path):
    root = _room(tmp_path)
    cell = spec.load_cell(TOY_CELL, root)
    assert Path(cell.arch.__file__) == (root / "bench" / "archs" /
                                        "toy.py").resolve()
    # the cuts reach the registry's config and the reference's sizes alike
    assert cell.cfg.num_layers == cell.dims.layers == 2
    assert cell.cfg.moe.num_experts == cell.dims.experts == 4
    # widths stay the published ones
    assert (cell.cfg.d_model, cell.cfg.num_kv_heads, cell.cfg.moe.top_k,
            cell.cfg.moe.d_ff_expert) == (4096, 8, 2, 6400)
    assert cell.max_seq_len == 64
    # the cells already there load unchanged beside it
    for name in PINNED:
        assert spec.load_cell(name, root).arch.__name__ != cell.arch.__name__


def _toy_conf(**edit):
    conf = json.loads(json.dumps(TOY_CONFIG))
    for path, value in edit.items():
        *outer, key = path.split("__")
        d = conf
        for k in outer:
            d = d[k]
        if value is None:
            del d[key]
        else:
            d[key] = value
    return conf


@pytest.mark.parametrize("edit, match", [
    ({"published__num_hidden_layers": 31}, "published num_hidden_layers"),
    ({"published__num_local_experts": 8}, "published num_local_experts"),
    ({"config__hidden_size": 4000}, "config file hidden_size"),
    ({"published__num_hidden_layers": None}, "no published value"),
    ({"arch": None}, "no architecture"),
    ({"arch": "no_such_arch"}, "unknown architecture"),
    ({"arch": "dense_gqa"}, "not dense GQA"),
])
def test_a_configuration_that_does_not_hold_raises(tmp_path, edit, match):
    root = _room(tmp_path, _toy_conf(**edit))
    with pytest.raises(ValueError, match=match):
        spec.load_cell(TOY_CELL, root)


def test_correct_reads_the_toy_forward_pass(tmp_path, monkeypatch):
    root = _room(tmp_path)
    cell = _paths.smoke_cell(TOY_CELL, root, max_seq_len=40)
    assert cell.dims.experts == 4 and cell.dims.layers == 2
    params = weights.make(cell.arch.shapes(cell.dims), seed=7)
    assert params["layers"]["mlp"]["w_up"].shape == (2, 4, 64, 64)
    calls = []
    forward = cell.arch.logits

    def spy(params, tokens, d, fp8=False):
        calls.append(fp8)
        return forward(params, tokens, d=d, fp8=fp8)

    # served tokens: the toy forward pass's own greedy picks
    lens, out_len = {0: 9, 1: 14}, 6
    tokens = {}
    for r, n in lens.items():
        seq = np.zeros(cell.max_seq_len, np.int32)
        seq[:n] = traffic.prompt_tokens(7, r, n, cell.dims.vocab)
        got = []
        for t in range(out_len):
            lg = np.asarray(forward(params, seq, d=cell.dims))
            got.append(int(lg[n - 1 + t].argmax()))
            seq[n + t] = got[-1]
        tokens[r] = got
    served = types.SimpleNamespace(
        arrivals=[types.SimpleNamespace(req_id=r, prompt_len=n)
                  for r, n in lens.items()],
        finished=set(lens), tokens=tokens)
    monkeypatch.setattr(cell.arch, "logits", spy)
    read = correct.readings(cell, params, 7, served, control=True)
    assert calls == [False, True, False, True]
    assert read["requests"] == 2 and read["tokens"] == 2 * out_len
    assert read["max_logit_gap"] <= 1e-5


if __name__ == "__main__":
    print(json.dumps(_readings()))
