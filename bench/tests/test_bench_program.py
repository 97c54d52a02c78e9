"""The program's spans and scopes in a trace: the joins of
``harness/program.py``, the HLO reader, and the five metrics that read
them, on synthetic spans and traces and on small CPU runs."""

import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import _paths
from harness import hlo, program, report, serve, trace as trace_lib, traffic

OFFSET = 1000.0          # the trace's clock less the host's, in ns
WQ = "jit(_decode_fn)/decode_layers/while/body/closed_call/layers/attn/wq/" \
     "dot_general"
DUS = "jit(_decode_fn)/decode_layers/while/body/dynamic_update_slice"
WALK = "jit(_decode_fn)/decode_layers/while/body/closed_call/layers/attn/" \
       "page_walk/jit(_fused_decode_pallas)/pallas_call"
HEAD = "jit(_decode_fn)/lm_head/dot_general"


def _span(name, a, b, sid, step, **counts):
    return program.Span(name, a, b, sid, step, int(a - OFFSET), counts)


def _spans():
    rows = [("decode.dispatch", 2000, 4000, 0), ("decode.read_tokens", 4000,
            10000, 0), ("decode.bookkeep", 10000, 12000, 0),
            ("schedule", 12000, 13000, 0), ("prefill.pad", 13000, 14000, 0),
            ("prefill.call", 14000, 20000, 0),
            ("prefill.slice", 20000, 22000, 0),
            ("kv.write_prefill", 22000, 30000, 0),
            ("admit.first_token", 30000, 33000, 0),
            ("admit.tables", 33000, 35000, 0),
            ("decode.dispatch", 40000, 42000, 1),
            ("decode.read_tokens", 42000, 50000, 1),
            ("decode.bookkeep", 50000, 51000, 1),
            ("schedule", 51000, 52000, 1),
            ("decode.dispatch", 60000, 61000, 2)]
    return [_span(n, a, b, i, st) for i, (n, a, b, st) in enumerate(rows)]


def _ev(name, a, b):
    return trace_lib.Event(name, a, b - a)


def _device_trace():
    ops = [_ev("%while.5 = (f32[2]) while(...)", 4000, 9000),
           _ev("%fusion.1 = bf16[4,96] fusion(...)", 4000, 6000),
           _ev("%fusion.2 = f32[8] fusion(...)", 6000, 7000),
           _ev("%_fused_decode_pallas.9 = bf16[4] custom-call()", 7000, 9000),
           _ev("%copy.3 = f32[8] copy(...)", 23000, 29000),
           _ev("%fusion.1 = bf16[4,96] fusion(...)", 45000, 47000),
           _ev("%fusion.2 = f32[8] fusion(...)", 47000, 48000),
           _ev("%convolution.9 = bf16[4,32] convolution(...)", 48000, 49000)]
    runs = [_ev("jit_scatter(9)", 1200, 1800),      # dispatched before
            _ev("jit__decode_fn(1)", 4000, 9000),
            _ev("jit_scatter(7)", 23000, 29000),
            _ev("jit__decode_fn(1)", 45000, 49000)]
    host = [_ev("PjitFunction(_decode_fn)", 2400, 3000),
            _ev("PJRT_LoadedExecutable_Execute", 2500, 2900),
            _ev("PjitFunction(scatter)", 22400, 22900),
            _ev("PJRT_LoadedExecutable_Execute", 22500, 22800),
            _ev("PjitFunction(_decode_fn)", 40400, 41000),
            _ev("PJRT_LoadedExecutable_Execute", 40500, 40900)]
    return trace_lib.DeviceTrace([ops], [runs], host, 100e-6)


NAMES = {"while.5": ("while", "jit(_decode_fn)/decode_layers/while"),
         "fusion.1": ("fusion", WQ), "fusion.2": ("fusion", DUS),
         "_fused_decode_pallas.9": ("custom-call", WALK),
         "convolution.9": ("convolution", HEAD)}


@pytest.fixture
def reading(monkeypatch):
    spans = _spans()
    monkeypatch.setattr(program, "newest", lambda d: "synthetic.xplane.pb")
    monkeypatch.setattr(program, "load_spans", lambda path: spans)
    monkeypatch.setattr(program.ProgramTrace, "hlo",
                        lambda self, fragment: dict(NAMES))
    served = SimpleNamespace(profile=(0.0, 100e-6, "dir"))
    return report.Reading(cell=None, served=served,
                          device_kind="TPU v5 lite", trace=_device_trace())


def test_the_five_readers_on_a_synthetic_trace(reading):
    read = {n: report.reader(n)(reading) for n in (
        "page_write_ms", "idle_in_host_work_share", "host_self_ms",
        "decode_gemm_ms", "decode_pool_copy_ms")}
    # one admitted request, whose page writes ran 6 us on the device
    assert read["page_write_ms"] == pytest.approx(0.006)
    # idle in host work: 2 (dispatch) + 12 (bookkeep, prefill.*, write) +
    # 5 (write, tables, dispatch) + 2 (bookkeep, dispatch) us of 100
    assert read["idle_in_host_work_share"] == pytest.approx(21.0)
    # steps 0 and 1: 38 - 9 and 20 - 8 us of their own
    assert read["host_self_ms"] == pytest.approx(0.0205)
    # per decode call: wq 2 + 2 us and the head 1 us; the scan's copy 1 us
    assert read["decode_gemm_ms"] == pytest.approx(0.0025)
    assert read["decode_pool_copy_ms"] == pytest.approx(0.001)


def test_readers_read_nothing_untraced_or_without_spans_and_scopes(
        reading, monkeypatch):
    names = ("page_write_ms", "idle_in_host_work_share", "host_self_ms",
             "decode_gemm_ms", "decode_pool_copy_ms")
    untraced = report.Reading(cell=None, device_kind="TPU v5 lite",
                              served=SimpleNamespace(profile=None))
    assert all(report.reader(n)(untraced) is None for n in names)
    # a program that mirrors no span and scopes nothing (the parent's)
    monkeypatch.setattr(program, "load_spans", lambda path: [])
    monkeypatch.setattr(program.ProgramTrace, "hlo", lambda self, f: {
        k: (op, "jit(_decode_fn)/while/body/closed_call/dot_general")
        for k, (op, _) in NAMES.items()})
    assert all(report.reader(n)(reading) is None for n in names)


def test_clock_join_maps_the_profile_onto_the_trace():
    spans = _spans()
    assert set(program.clock_offsets(spans)) == {OFFSET}
    assert program.to_trace_clock(spans, 2.0) == pytest.approx(2e9 + OFFSET)


def test_dispatch_join_skips_runs_dispatched_before_the_trace():
    dt = _device_trace()
    pairs = program.dispatches(program.executes_of(dt.host),
                               dt.programs_by_device[0])
    assert [(e.name, r.name) for e, r in pairs] == [
        ("_decode_fn", "jit__decode_fn(1)"), ("scatter", "jit_scatter(7)"),
        ("_decode_fn", "jit__decode_fn(1)")]
    spans = _spans()
    by_span = program.runs_in(pairs, spans)
    assert [r.name for r in by_span[7]] == ["jit_scatter(7)"]   # the write
    assert [r.name for r in by_span[0]] == ["jit__decode_fn(1)"]
    # a call dispatched as the trace ended has no run in it; a run may read
    # a little earlier than its dispatch, and a call may name its program
    # otherwise: the queue's order still pairs them
    late = program.executes_of(dt.host) + [trace_lib.Event("argmax", 5e4, 1)]
    early = [trace_lib.Event(r.name, r.start_ns - 600, r.dur_ns)
             for r in dt.programs_by_device[0]]
    early[2] = trace_lib.Event("jit_dynamic_slice(3)", early[2].start_ns, 1)
    assert [(e.name, r.name) for e, r in program.dispatches(late, early)] \
        == [("_decode_fn", "jit__decode_fn(1)"),
            ("scatter", "jit_dynamic_slice(3)"),
            ("_decode_fn", "jit__decode_fn(1)")]


def test_scope_classes_of_real_op_names():
    assert program.is_gemm(WQ) and program.is_gemm(HEAD)
    assert not program.is_gemm(DUS) and not program.is_gemm(WALK)
    assert program.is_pool_copy(DUS)
    assert program.is_pool_copy(
        "jit(_decode_fn)/decode_layers/while/body/squeeze")
    assert not program.is_pool_copy(WQ) and not program.is_pool_copy(WALK)
    assert not program.is_pool_copy("jit(_decode_fn)/while/body/squeeze")
    assert program.in_scope("page_walk")(WALK)


def test_overlap_of_unions_and_steps_without_a_successor():
    assert program.overlap_ns([(0, 10), (5, 20), (30, 40)],
                              [(15, 35)]) == 5 + 5
    assert program.step_self_ns([_span("schedule", 0, 5, 0, 4)]) == []


def test_hlo_reader_names_the_ops_of_a_cpu_trace(tmp_path):
    # the trace holds every program of the process: a name no engine uses,
    # so an engine built by an earlier test in this process is not found
    @jax.jit
    def _decode_fn_of_this_test(x):
        with jax.named_scope("decode_layers"), jax.named_scope("wq"):
            return jnp.tanh(x @ x)

    x = jnp.ones((64, 64))
    _decode_fn_of_this_test(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    _decode_fn_of_this_test(x).block_until_ready()
    jax.profiler.stop_trace()
    found = hlo.op_names(program.newest(str(tmp_path)),
                         "_decode_fn_of_this_test")
    assert len(found) == 1
    names = next(iter(found.values()))
    assert any(op_name.endswith("decode_layers/wq/dot_general")
               for _, op_name in names.values())
    assert hlo.op_names(program.newest(str(tmp_path)), "no_such") == {}


def test_wire_reader_walks_nested_messages():
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(num, payload):
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    inst = field(1, b"fusion.7") + field(2, b"fusion") + field(
        7, field(2, b"jit(f)/decode_layers/add")) + varint(35 << 3) + \
        varint(300)
    proto = field(1, field(3, field(2, inst)))
    buf = memoryview(b"xx" + proto)
    assert hlo.instructions(buf, (2, len(buf))) == {
        "fusion.7": ("fusion", "jit(f)/decode_layers/add")}


@pytest.fixture(scope="module")
def smoke():
    from repro.serving.engine import _bucket
    mix = {"arrivals": "poisson",
           "prompt": {"dist": "uniform", "min": 5, "max": 40},
           "output": {"dist": "uniform", "min": 2, "max": 9}}
    cell = _paths.smoke_cell("phi3-mini-3.8b.float.longgen", traffic=mix,
                             max_batch=3, page_size=4, max_seq_len=64)
    params = serve.weights.make(cell.arch.shapes(cell.dims), seed=5)
    engine = serve.build_engine(cell, params, seed=5)
    arrivals = traffic.arrivals(mix, 20.0, 0.6, seed=5)
    serve.warm_up(cell, engine, arrivals, _bucket)
    return cell, engine, arrivals


def test_an_untraced_window_records_nothing(smoke, monkeypatch):
    from repro.serving import spans as spans_lib
    cell, engine, arrivals = smoke
    made, kwargs = [], []
    monkeypatch.setattr(spans_lib.SpanRecorder, "__init__",
                        lambda self, *a, **k: made.append(1))
    orig = engine.run

    def run(*a, **k):
        kwargs.append(k)
        return orig(*a, **k)

    monkeypatch.setattr(engine, "run", run)
    served = serve.window(cell, engine, arrivals, 0.1)
    assert served.profile is None and len(served.finished) == len(arrivals)
    assert kwargs == [{}] and made == []


def test_program_stamps_agree_with_the_scheduler(smoke):
    from repro.serving.spans import SpanRecorder
    cell, engine, arrivals = smoke
    sched = serve._scheduler(cell, arrivals)
    sched.open(time.perf_counter())
    rec = SpanRecorder()
    engine.run(serve._requests(arrivals), sched, spans=rec)
    sched.release()
    dump = rec.dump()
    first = {e["req"]: e["t_ns"] * 1e-9 for e in dump["events"]
             if e["name"] == "first_token"}
    assert set(first) == set(sched.first_at)
    assert all(abs(first[r] - t) < 1e-3 for r, t in sched.first_at.items())
    rows = [s["counts"]["rows"] for s in dump["spans"]
            if s["name"] == "serve.step" and s["counts"]["rows"]]
    assert rows == [r for _, r, _ in sched.decode_steps]
