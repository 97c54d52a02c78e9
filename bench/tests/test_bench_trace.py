"""Reduction of a profiler trace, on a small trace recorded on the CPU."""

import time

import pytest

import _paths  # noqa: F401
from harness import serve, trace


def test_union_and_gaps_of_overlapping_events():
    ev = [trace.Event("a", 0, 10), trace.Event("b", 5, 10),
          trace.Event("c", 30, 5), trace.Event("d", 31, 1)]
    assert trace.union_ns(ev) == 15 + 5
    assert trace.gaps(ev, 0, 40) == [(15, 30), (35, 40)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def prefill_fn(x):
        return jnp.tanh(x @ x).sum()

    @jax.jit
    def _decode_fn(x):
        return (x * 2.0).sum()

    x = jnp.ones((384, 384))
    prefill_fn(x).block_until_ready()
    _decode_fn(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d, profiler_options=serve._profile_options())
    t0 = time.perf_counter()
    for _ in range(3):
        prefill_fn(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.05)
        _decode_fn(x).block_until_ready()
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    return trace.load(d, window), window


def test_named_programs_are_found(recorded):
    dt, _ = recorded
    assert len(dt.programs("prefill_fn")) == 3
    assert len(dt.programs("_decode_fn")) == 3
    assert dt.ops("prefill_fn") and dt.ops("_decode_fn")
    assert not dt.programs("no_such_program")


def test_busy_union_and_idle_share(recorded):
    dt, window = recorded
    assert dt.window_s >= window
    busy = dt.busy_s
    assert 0 < busy < window - 0.12      # three 50 ms sleeps are idle
    ops = dt.ops_by_device[0]
    assert busy <= sum(e.seconds for e in ops) + 1e-12
    idle = 1 - busy / window
    assert 0.3 < idle < 1


def test_breakdown_names_ops_and_idle_gaps(recorded):
    dt, _ = recorded
    b = dt.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in b["device_ops"] + b["idle_gaps"])
    # the longest gaps are the sleeps, named by the host span around them
    assert [n for n, _ in b["idle_gaps"][:3]] == ["bench.sleep"] * 3
