"""Metric arithmetic, counts of operations and bytes, and the peaks table."""

import math

import pytest

import _paths  # noqa: F401
from harness import counts, spec, timeline


def _serve(seconds=20.0, step=0.02, rate=2.0, out=20, stalls=()):
    """A synthetic server: a request every 1/rate s, admitted at the next
    step, one token per step; a stall (start, length) holds every step."""
    token_at, due_at, first_at = {}, {}, {}
    n = int(seconds * rate)
    for r in range(n):
        due = r / rate
        t = due
        times = []
        while len(times) < out:
            t += step
            for a, length in stalls:
                if a <= t < a + length:
                    t = a + length + step
            times.append(t)
        token_at[r], due_at[r], first_at[r] = times, due, times[0]
    return token_at, due_at, first_at


def test_rate_is_over_the_whole_window():
    token_at = {0: [0.5, 1.5, 2.5, 9.5, 10.5], 1: [3.0, 11.0]}
    assert timeline.output_tok_s(token_at, 0.0, 10.0) == 5 / 10.0
    assert timeline.output_tok_s(token_at, 1.0, 10.0) == 5 / 10.0


def test_gaps_count_first_to_second_and_only_in_window():
    token_at = {0: [1.0, 1.5, 3.0], 1: [9.8, 10.4]}
    assert sorted(timeline.token_gaps(token_at, 0.0, 10.0)) == [0.5, 1.5]


def test_a_stall_moves_itl_and_ttft_tails():
    calm = _serve(out=5)
    stalled = _serve(out=5, stalls=[(t + dt, 0.3) for t in range(20)
                                           for dt in (0.04, 0.5)])
    itl = [timeline.percentile(timeline.token_gaps(tok, 0.0, 20.0), 95)
           for tok, _, _ in (calm, stalled)]
    ttft = [timeline.percentile(timeline.ttfts(due, first), 90)
            for _, due, first in (calm, stalled)]
    assert itl[0] == pytest.approx(0.02)
    assert itl[1] > 0.25
    assert ttft[0] == pytest.approx(0.02)
    assert ttft[1] > 0.2


def test_a_request_that_never_starts_is_infinitely_late():
    due = {0: 0.0, 1: 1.0}
    assert timeline.ttfts(due, {0: 0.5}) == [0.5, math.inf]
    assert timeline.percentile([0.5, math.inf], 90) == math.inf
    assert timeline.percentile([1.0, 2.0, 3.0], 50) == 2.0


def test_occupancy_is_rows_over_slots_of_decode_steps():
    steps = [(0.1, 8, 0), (0.2, 4, 0), (0.3, 6, 0)]
    assert timeline.occupancy(steps, 8) == pytest.approx(18 / 24)
    assert timeline.occupancy([], 8) is None


def _dims(name):
    conf = spec.read_json(_paths.BENCH / "configs" / f"{name}.json")
    return spec.load_arch(conf["arch"]).Dims.from_config(conf["config"])


def test_internlm2_decode_step_by_hand():
    d = _dims("internlm2-1.8b.float")
    # per layer: 2048*128*(2*16 + 2*8) + 3*2048*8192 = 62,914,560;
    # 24 layers + the 2048 x 92544 head
    assert counts.matmul_weights(d) == 1_699_479_552
    # 8 rows at 512 positions each: 2*W*8 + 4*24*16*128*4096
    assert counts.decode_step_flops(d, 8, 8 * 512) == 27_996_979_200


def test_phi3_decode_step_by_hand():
    d = _dims("phi3-mini-3.8b.float")
    # per layer: 3072*96*(64 + 64) + 3*3072*8192 = 113,246,208;
    # 32 layers + the 3072 x 32064 head
    assert counts.matmul_weights(d) == 3_722_379_264
    # 4 rows at 600 positions each: 2*W*4 + 4*32*32*96*2400
    assert counts.decode_step_flops(d, 4, 4 * 600) == 30_722_752_512


def test_one_fused_kernel_call_by_hand():
    import dataclasses
    # one call walks one layer: internlm2, 8 rows of 512 positions
    d = dataclasses.replace(_dims("internlm2-1.8b.float"), layers=1)
    assert counts.kv_bytes(d, 4096) == 2 * 8 * 128 * 2 * 4096 == 16_777_216
    assert counts.attention_flops(d, 4096) == 4 * 16 * 128 * 4096
    peak = counts.peaks("TPU v5 lite")
    least = counts.roofline_seconds(33_554_432, 16_777_216, peak)
    assert least == pytest.approx(16_777_216 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        counts.peaks("TPU v9")
    assert counts.peaks("TPU v5 lite")["bf16_flop_s"] == 197e12


def test_latency_layers_read_only_what_was_due_before_the_profiler():
    """Stopping the profiler holds the host loop: the per-layer latency
    readers leave out the requests due after it started."""
    from types import SimpleNamespace

    from harness import report
    arrivals = [SimpleNamespace(req_id=r, due_s=float(r)) for r in range(10)]
    late = {r: (9.0 if r >= 5 else 0.1) for r in range(10)}
    sched = SimpleNamespace(
        first_at={r: 100.0 + r + late[r] for r in range(10)},
        picked_at={r: 100.0 + r + late[r] / 2 for r in range(10)})
    served = SimpleNamespace(arrivals=arrivals, start=100.0,
                             scheduler=sched, profile=(104.5, 110.0, None))
    r = report.Reading(cell=None, served=served, device_kind="TPU v5 lite")
    assert sorted(r.due_before_trace()) == [0, 1, 2, 3, 4]
    assert report.reader("ttft_p90_pre_trace_s")(r) == pytest.approx(0.1)
    assert report.reader("queue_wait_p90_s")(r) == pytest.approx(0.05)
    assert report.reader("ttft_p50_s")(r) == pytest.approx(4.55)
    served.profile = None
    assert report.reader("ttft_p90_pre_trace_s")(r) is None
    assert report.reader("queue_wait_p90_s")(r) is None
