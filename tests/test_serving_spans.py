"""The serving loop's own spans, counters and events (``serving/spans.py``),
their mirror in a profiler trace, and the decode step's named scopes."""

import dataclasses
import gc
import glob
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import model as model_lib
from repro.serving import ServingEngine, TrafficConfig, generate_trace
from repro.serving import spans as spans_lib
from repro.serving.paged_kv import PagedKVCache

LEAVES = {"decode.dispatch", "decode.read_tokens", "decode.bookkeep",
          "schedule", "admit.prompts", "prefill.pad", "prefill.call",
          "prefill.slice", "admit.first_token", "kv.write_prefill",
          "admit.tables"}


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(configs.get_smoke_config("llama3-8b"),
                               compute_dtype="float32",
                               param_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(0))


def _trace(seed=0, n=6):
    return generate_trace(TrafficConfig(
        num_requests=n, arrival_rate=1.0, prompt_short=(2, 5),
        prompt_long=(6, 10), output_short=(2, 4), output_long=(5, 8),
        p_long=0.4, seed=seed))


def _engine(cfg, params):
    return ServingEngine(cfg, params, max_batch=3, page_size=4,
                         max_seq_len=32)


@pytest.fixture(scope="module")
def served(cfg, params):
    eng = _engine(cfg, params)
    trace = _trace()
    off = eng.run(trace)
    rec = spans_lib.SpanRecorder()
    on = eng.run(trace, spans=rec)
    return trace, eng, off, on, rec.dump()


def test_recorder_off_records_nothing_and_serves_the_same(served, cfg,
                                                          params,
                                                          monkeypatch):
    trace, _, off, on, _ = served
    assert on.events == off.events
    assert on.request_tokens == off.request_tokens
    assert (on.steps, on.tokens) == (off.steps, off.tokens)
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **k):
            made.append(a)
            super().__init__(*a, **k)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    monkeypatch.setattr(spans_lib.SpanRecorder, "__init__",
                        lambda self, *a, **k: made.append("recorder"))
    again = _engine(cfg, params).run(trace)
    assert made == []
    assert again.events == off.events
    assert spans_lib._LIVE == []
    assert spans_lib.NULL.span("x") is spans_lib.NULL.span("y", req=3)


def test_span_tree_is_well_formed(served):
    _, _, _, report, dump = served
    spans = dump["spans"]
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans)
    roots = [s for s in spans if s["parent"] == -1]
    assert [s["name"] for s in roots] == ["serve.run"]
    for s in spans:
        assert s["end_ns"] >= s["start_ns"]
        if s["parent"] != -1:
            p = by_id[s["parent"]]
            assert p["start_ns"] <= s["start_ns"] <= s["end_ns"] <= p["end_ns"]
    steps = [s for s in spans if s["name"] == "serve.step"]
    assert len(steps) == report.steps
    assert [s["step"] for s in steps] == list(range(report.steps))
    leaves = [s for s in spans if s["name"] not in ("serve.run", "serve.step")]
    assert {s["name"] for s in leaves} == LEAVES
    assert all(by_id[s["parent"]]["name"] == "serve.step" for s in leaves)


def test_events_tokens_and_pages(served):
    trace, eng, _, report, dump = served
    events = dump["events"]
    for r in trace:
        mine = [e["name"] for e in events if e["req"] == r.req_id]
        assert mine == ["first_token", "admitted", "evicted"]
    # the report's stream is the recorder's admissions and evictions
    kinds = {"admitted": "admit", "evicted": "evict"}
    assert report.events == tuple((e["step"], kinds[e["name"]], e["req"])
                                  for e in events if e["name"] in kinds)
    steps = [s for s in dump["spans"] if s["name"] == "serve.step"]
    assert sum(s["counts"]["tokens"] for s in steps) == report.tokens \
        == sum(r.output_len for r in trace)
    assert sum(s["counts"]["rows"] for s in steps) == sum(
        r.output_len - 1 for r in trace)
    writes = [s for s in dump["spans"] if s["name"] == "kv.write_prefill"]
    assert sorted(w["req"][0] for w in writes) == sorted(
        r.req_id for r in trace)
    assert sum(w["counts"]["pages"] for w in writes) == sum(
        math.ceil(r.prompt_len / eng.page_size) for r in trace)
    # one device program writes a request's pages, however many they are
    assert all(w["counts"]["dispatches"] == 1 for w in writes)
    first = {e["req"]: e["t_ns"] for e in events if e["name"] == "first_token"}
    for s in dump["spans"]:
        if s["name"] == "admit.first_token":
            assert s["start_ns"] <= first[s["req"][0]]


def test_jit_trace_counter_reads_new_shapes_and_not_a_warm_loop(cfg,
                                                                 params):
    # a configuration no other engine of the process has compiled for
    fresh = dataclasses.replace(cfg, rms_eps=cfg.rms_eps * 1.5)
    eng = _engine(fresh, params)
    trace = _trace(seed=3)
    cold = spans_lib.SpanRecorder()
    eng.run(trace, spans=cold)
    calls = [s for s in cold.dump()["spans"] if s["name"] == "prefill.call"]
    assert calls[0]["counts"].get("jit_traces", 0) >= 1
    warm = spans_lib.SpanRecorder()
    eng.run(trace, spans=warm)
    assert not any(s["counts"].get("jit_traces") or s["counts"].get(
        "compiles") for s in warm.dump()["spans"])


def test_counters_credit_the_innermost_open_span():
    rec = spans_lib.SpanRecorder()
    with rec.span("outer", leaf=False):
        with rec.span("gc") as s:
            gc.collect()
            s.count(pages=2)
        with rec.span("jit"):
            jax.jit(lambda x: x * 3.0 + 1.25)(jnp.ones(11)).block_until_ready()
    jax.jit(lambda x: x * 5.0 - 1.5)(jnp.ones(13)).block_until_ready()
    spans = {s["name"]: s["counts"] for s in rec.dump()["spans"]}
    assert spans["gc"]["gc"] >= 1 and spans["gc"]["gc_ms"] >= 0
    assert spans["gc"]["pages"] == 2
    assert spans["jit"]["jit_traces"] >= 1 and spans["jit"]["compiles"] >= 1
    assert "jit_traces" not in spans["outer"]
    assert spans_lib._LIVE == []


def _mirrored(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "span_id" in st and "host_ns" in st:
                    out[int(st["span_id"])] = (e.name, e.start_ns, st)
    return out


def test_clock_join_on_a_cpu_profile(served, tmp_path):
    trace, eng, _, _, _ = served
    rec = spans_lib.SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run(trace, spans=rec)
    finally:
        jax.profiler.stop_trace()
    found = _mirrored(str(tmp_path))
    leaves = [s for s in rec.dump()["spans"] if s["name"] in LEAVES]
    assert leaves and all(s["id"] in found for s in leaves)
    offsets = []
    for s in leaves:
        name, start, stats = found[s["id"]]
        assert name == s["name"] and int(stats["host_ns"]) == s["start_ns"]
        assert int(stats["step"]) == s["step"]
        offsets.append(start - s["start_ns"])
    assert max(offsets) - min(offsets) < 1e6
    writes = [found[s["id"]][2] for s in leaves
              if s["name"] == "kv.write_prefill"]
    assert all(int(w["pages"]) >= 1 for w in writes)


def test_an_unrecorded_loop_follows_the_profiler(served, tmp_path):
    trace, eng, off, _, _ = served
    jax.profiler.start_trace(str(tmp_path))
    try:
        report = eng.run(trace)
    finally:
        jax.profiler.stop_trace()
    assert report.events == off.events
    names = {name for name, _, _ in _mirrored(str(tmp_path)).values()}
    assert names == LEAVES
    assert spans_lib.follow_profiler(spans_lib.NULL) is spans_lib.NULL


def test_decode_program_carries_its_scopes(served, cfg):
    _, eng, _, _, _ = served
    cache = PagedKVCache(num_layers=cfg.num_layers,
                         num_kv_heads=cfg.num_kv_heads,
                         head_dim=cfg.resolved_head_dim,
                         num_pages=eng.num_pages, page_size=eng.page_size,
                         max_seq_len=eng.max_seq_len)
    b = eng.max_batch
    with jax.set_mesh(eng._mesh):
        text = eng._decode.lower(
            eng._exec_params, jnp.zeros((b, 1), jnp.int32), cache.k_pool,
            cache.v_pool, jnp.zeros((b, cache.max_blocks), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool)
        ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("decode_layers/", "layers/attn/wq/", "layers/attn/wo/",
                  "layers/mlp/w_up/", "layers/attn/kv_write/",
                  "layers/attn/page_walk/", "lm_head/"):
        assert any(scope in n for n in names), scope
    # the scan's own slicing of each layer's pools lies under no site scope
    assert any("decode_layers/" in n and "/layers/" not in n
               and "dynamic_update_slice" in n for n in names)
