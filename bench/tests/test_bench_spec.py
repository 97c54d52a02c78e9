"""BENCHMARK.json against the shape the benchmark's checker expects."""

import re

import pytest

import _paths
from harness import report, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _text_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_entries_have_just_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and _text_ok(c["why"])
        assert (_paths.ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _text_ok(w["why"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_names_units_and_uniqueness():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert {(w["config"], w["traffic"]) for w in BENCH["workloads"]} \
        .__len__() == len(BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_it_must(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"]
           if report.applies(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in BENCH["per_layer"] if report.applies(m, cell)]
    assert layer
    for m in layer:
        assert m["moves"] in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (_paths.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert (_paths.BENCH / "cells" / f"{cell}.json").is_file()


def test_per_layer_metrics_name_one_layer_each():
    for m in BENCH["per_layer"]:
        assert _text_ok(m["layer"])
        assert m["moves"] in [e["name"] for e in BENCH["end_to_end"]]
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_the_check_fits_with_a_full_benchmark():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


# a width: hidden, intermediate, latent, state or projection sizes, keys
# ending in _dim or _rank, head sizes and counts, expansion factors, experts
# per token.  Depth (num_hidden_layers) and the experts held may be cut.
WIDTHS = re.compile(r"(hidden_size|hidden_dim|intermediate|latent|state|"
                    r"projection|head|expan|_dim$|_rank$|experts_per_tok)")


@pytest.mark.parametrize("key", [
    "hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
    "v_head_dim", "num_attention_heads", "num_key_value_heads",
    "kv_lora_rank", "q_lora_rank", "ssm_state_size", "mamba_d_state",
    "mamba_expand", "expansion_factor", "num_experts_per_tok",
    "projection_size", "kv_latent_dim"])
def test_a_width_may_not_be_cut(key):
    assert WIDTHS.search(key)


@pytest.mark.parametrize("key", [
    "num_hidden_layers", "num_local_experts", "num_experts",
    "n_routed_experts", "max_position_embeddings"])
def test_depth_experts_held_and_context_may_be_cut(key):
    assert not WIDTHS.search(key)


def test_configs_are_used_and_reduce_no_width():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert len(c["reduced"]) <= 16
        assert not any(WIDTHS.search(k) for k in c["reduced"])
        conf = spec.read_json(_paths.ROOT / c["file"])
        assert set(c["reduced"]) == set(conf["reduced"])
        assert set(conf["reduced"]) <= set(conf["published"])
        assert spec.load_arch(conf["arch"]).__name__ == f"archs.{conf['arch']}"
