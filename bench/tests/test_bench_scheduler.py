"""The open-loop scheduler object, alone and driving ServingEngine.run."""

import dataclasses

import pytest

import _paths  # noqa: F401
from harness import serve, spec, traffic
from harness.scheduler import OpenLoopScheduler
from repro.serving.scheduler import Request
from repro.serving.traffic import TrafficRequest


class FakeClock:
    def __init__(self):
        self.t = 0.0
        self.slept = []

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.slept.append(dt)
        self.t += dt


class FakeCache:
    page_size = 4

    def __init__(self, free=100):
        self.allocator = type("A", (), {"num_free": free})()

    def pages_needed(self, n):
        return -(-n // self.page_size)

    def block_table_row(self, req_id=None):
        return req_id


def _waiting(n, prompt=8, out=4):
    return [Request(spec=TrafficRequest(i, 0, prompt, out)) for i in range(n)]


def _sched(due, clock, max_batch=4):
    s = OpenLoopScheduler(max_batch, due, {r: 8 for r in due}, clock=clock,
                          sleep=clock.sleep)
    s.open(0.0)
    return s


def test_nothing_is_admitted_before_it_is_due():
    clock = FakeClock()
    s = _sched({0: 1.0, 1: 2.0, 2: 2.5}, clock)
    waiting = _waiting(3)
    clock.t = 0.5
    assert s.admissions(0, waiting, 1, FakeCache()) == []
    clock.t = 1.0
    assert [r.req_id for r in s.admissions(1, waiting, 1, FakeCache())] == [0]
    clock.t = 2.6
    got = s.admissions(2, waiting[1:], 1, FakeCache())
    assert [r.req_id for r in got] == [1, 2]
    assert s.picked_at == {0: 1.0, 1: 2.6, 2: 2.6}


def test_fifo_slots_and_pages_bound_admission():
    clock = FakeClock()
    s = _sched({i: 0.0 for i in range(6)}, clock, max_batch=4)
    waiting = _waiting(6)
    assert [r.req_id for r in s.admissions(0, waiting, 1, FakeCache())] \
        == [0, 1, 2]
    s2 = _sched({i: 0.0 for i in range(6)}, FakeClock())
    # each request needs 3 pages: 7 free pages hold two
    assert [r.req_id for r in s2.admissions(0, waiting, 0, FakeCache(7))] \
        == [0, 1]


def test_idle_sleeps_to_the_next_due_time_and_never_spins():
    clock = FakeClock()
    s = _sched({0: 3.0, 1: 3.2}, clock)
    waiting = _waiting(2)
    got = s.admissions(0, waiting, 0, FakeCache())
    assert [r.req_id for r in got] == [0]
    assert clock.slept == [3.0] and s.idle_sleeps == 1
    # something runs: no sleep even though request 1 is not due yet
    assert s.admissions(1, waiting[1:], 1, FakeCache()) == []
    assert clock.slept == [3.0]


def test_tokens_are_credited_to_the_boundary_that_produced_them():
    clock = FakeClock()
    s = _sched({0: 0.0}, clock)
    waiting = _waiting(1, out=3)
    cache = FakeCache()
    (req,) = s.admissions(0, waiting, 0, cache)
    clock.t = 0.25
    cache.block_table_row(0)          # run() reads the row after the token
    req.generated = 1
    clock.t = 0.5
    req.generated = 2
    s.admissions(1, [], 1, cache)
    clock.t = 0.75
    req.generated = 3
    s.admissions(2, [], 0, cache)
    assert s.first_at == {0: 0.25}
    assert s.token_at[0] == [0.25, 0.5, 0.75]
    assert [(rows, ctx) for _, rows, ctx in s.decode_steps] == [(1, 9), (1, 10)]


@pytest.mark.parametrize("name,mode", [
    ("internlm2-1.8b.float.code", "float"),
    ("phi3-mini-3.8b.float.longgen", "float"),
    ("internlm2-1.8b.float.code", "tubgemm4")])
def test_drives_run_to_completion_on_smoke_configs(name, mode):
    from repro.serving.engine import _bucket
    mix = {"arrivals": "poisson",
           "prompt": {"dist": "uniform", "min": 5, "max": 40},
           "output": {"dist": "uniform", "min": 2, "max": 9}}
    cell = _paths.smoke_cell(
        name, traffic=mix, max_batch=3, page_size=4, max_seq_len=64,
        mode=spec.read_json(_paths.BENCH / "modes" / f"{mode}.json"))
    params = serve.weights.make(cell.arch.shapes(cell.dims), seed=3)
    engine = serve.build_engine(cell, params, seed=3)
    arrivals = traffic.arrivals(mix, 20.0, 0.6, seed=3)
    serve.warm_up(cell, engine, arrivals, _bucket)
    s = serve._scheduler(cell, arrivals)
    calls = []
    orig = s.admissions

    def counted(*a):
        calls.append(a[0])
        return orig(*a)

    s.admissions = counted
    s.open(serve.time.perf_counter())
    report = engine.run(serve._requests(arrivals), s)
    assert report.requests == len(arrivals) == 12
    out = {a.req_id: a.output_len for a in arrivals}
    assert {r: len(t) for r, t in s.token_at.items()} == out
    assert {r: len(t) for r, t in report.request_tokens.items()} == out
    assert set(s.first_at) == set(out)
    decoded = sum(rows for _, rows, _ in s.decode_steps)
    assert decoded == sum(out.values()) - len(out)
    # one call per step; a step either decodes or admits a due request
    assert len(calls) <= len(s.decode_steps) + len(out) + 1
    assert dataclasses.asdict(arrivals[0])["due_s"] == 0.0
