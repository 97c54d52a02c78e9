"""The traffic generator and the data-driven loading of cells."""

import json
import shutil

import numpy as np
import pytest

import _paths  # noqa: F401
from harness import spec, traffic

MIXES = sorted(p.stem for p in (_paths.BENCH / "traffic").glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_trace(mix):
    m = spec.read_json(_paths.BENCH / "traffic" / f"{mix}.json")
    a = traffic.arrivals(m, 2.0, 30, seed=2**31 + 12345)
    b = traffic.arrivals(m, 2.0, 30, seed=2**31 + 12345)
    assert a == b
    c = traffic.arrivals(m, 2.0, 30, seed=7)
    assert a != c


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work(mix):
    m = spec.read_json(_paths.BENCH / "traffic" / f"{mix}.json")
    runs = [traffic.arrivals(m, 2.5, 40, seed=s) for s in (1, 2, 3**20)]
    for field in ("prompt_len", "output_len"):
        sets = [sorted(getattr(a, field) for a in r) for r in runs]
        assert sets[0] == sets[1] == sets[2]
    gaps = [sorted(np.diff([a.due_s for a in r])) for r in runs]
    assert len({len(r) for r in runs}) == 1 == len({len(g) for g in gaps})


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_rotate_one_sequence(mix):
    """Every seed offers the mix's one sequence of requests, each with the
    gap that follows it, from another start after a lull: the same bursts
    in every run."""
    m = spec.read_json(_paths.BENCH / "traffic" / f"{mix}.json")
    seconds = 40

    def triples(seed):
        arr = traffic.arrivals(m, 2.5, seconds, seed=seed)
        ends = [a.due_s for a in arr[1:]] + [seconds]
        return [(a.prompt_len, a.output_len, round(end - a.due_s, 6))
                for a, end in zip(arr, ends)]

    base = triples(1)
    for seed in (2, 2**31 + 7):
        t = triples(seed)
        assert t != base
        assert any(t == base[k:] + base[:k] for k in range(len(base)))
        gaps = sorted(g for *_, g in t)
        assert t[-1][2] >= gaps[int(0.75 * len(gaps)) - 1]


@pytest.mark.parametrize("mix", MIXES)
def test_clips_medians_and_window(mix):
    m = spec.read_json(_paths.BENCH / "traffic" / f"{mix}.json")
    seconds, rate = 45, 2.2
    arr = traffic.arrivals(m, rate, seconds, seed=11)
    assert len(arr) == round(rate * seconds)
    assert [a.req_id for a in arr] == list(range(len(arr)))
    due = [a.due_s for a in arr]
    assert due[0] == 0.0 and all(b > a for a, b in zip(due, due[1:]))
    assert due[-1] < seconds
    for field, dist in (("prompt_len", m["prompt"]),
                        ("output_len", m["output"])):
        v = np.array([getattr(a, field) for a in arr])
        assert v.min() >= dist["min"] and v.max() <= dist["max"]
        med = dist.get("median", (dist["min"] + dist["max"]) / 2)
        assert abs(np.median(v) - med) <= 0.02 * med + 1


def test_prompt_tokens_seeded_and_in_vocab():
    a = traffic.prompt_tokens(2**33 + 1, 4, 100, 512)
    assert np.array_equal(a, traffic.prompt_tokens(2**33 + 1, 4, 100, 512))
    assert not np.array_equal(a, traffic.prompt_tokens(1, 4, 100, 512))
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < 512


def test_warmup_reaches_every_width_and_partial_page():
    from repro.serving.engine import _bucket
    m = {"prompt": {"min": 128, "max": 1024}}
    lens = traffic.warmup_prompt_lens(m, 16, _bucket)
    assert {_bucket(n) for n in lens} == {_bucket(n) for n in range(128, 1025)}
    assert {n % 16 for n in lens} == set(range(16))
    assert all(128 <= n <= 1024 for n in lens)


def test_a_new_mix_is_one_added_file(tmp_path):
    """A cell on a new traffic mix needs its files and its entry only."""
    root = tmp_path / "repo"
    shutil.copytree(_paths.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    mix = {"why": "test", "arrivals": "poisson",
           "prompt": {"dist": "uniform", "min": 16, "max": 48},
           "output": {"dist": "lognormal", "median": 8, "sigma": 0.3,
                      "min": 2, "max": 16}}
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    name = "internlm2-1.8b.float.tiny"
    (root / "bench" / "cells" / f"{name}.json").write_text(json.dumps(
        {"rate_per_s": 3.0, "limits": {"max_logit_gap": 1.0}}))
    bench["workloads"].append({"name": name, "config": "internlm2-1.8b.float",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(name, root)
    assert cell.traffic == mix and cell.rate_per_s == 3.0
    arr = traffic.arrivals(cell.traffic, cell.rate_per_s, 10, seed=5)
    assert len(arr) == 30 and all(16 <= a.prompt_len <= 48 for a in arr)


def test_cells_of_the_benchmark_load():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.cfg.d_model == cell.dims.d_model
        assert cell.cfg.param_dtype == "bfloat16"
        assert cell.max_seq_len >= (cell.traffic["prompt"]["max"]
                                    + cell.traffic["output"]["max"])


def test_config_file_must_match_the_registry(tmp_path):
    conf = spec.read_json(_paths.BENCH / "configs" /
                          "phi3-mini-3.8b.float.json")
    conf["config"]["hidden_size"] = 4096
    with pytest.raises(ValueError, match="d_model"):
        spec.model_config(conf)
