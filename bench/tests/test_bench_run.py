"""The command without a chip, and a whole run with its timed path broken."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

import _paths
from harness import report, spec

SMALL = {"arrivals": "poisson",
         "prompt": {"dist": "uniform", "min": 6, "max": 40},
         "output": {"dist": "uniform", "min": 4, "max": 12}}


def _cmd(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "internlm2-1.8b.float.code", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"], cwd=cwd, capture_output=True,
        text=True, timeout=300, env={**os.environ, **(env or {})})


def test_no_tpu_exits_nonzero_with_no_result():
    p = _cmd(_paths.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(_paths.ROOT / "BENCHMARK.json", tmp_path)
    p = _cmd(tmp_path, {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _run(name="internlm2-1.8b.float.code", seed=2**31 + 21):
    """A run of the cell's harness at smoke sizes: twelve requests due in
    a tenth of a second, so the four slots fill."""
    cell = _paths.smoke_cell(name, traffic=SMALL, rate_per_s=120.0,
                             max_batch=4, page_size=4, max_seq_len=64)
    return report.run(spec.load_benchmark(), cell, seed, 0.1, False,
                      t_proc=time.perf_counter(), trace_root=None)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_s", "setup_s"}
    assert list(out)[-1] == "checks"
    json.dumps(out)


def _state_unchanged(orig):
    def step(self, params, tokens, k_pool, v_pool, *rest):
        logits, _, _, lengths = orig(self, params, tokens, k_pool, v_pool,
                                     *rest)
        return logits, k_pool, v_pool, lengths
    return step


def _half_batch(orig):
    def step(self, params, tokens, k_pool, v_pool, tables, lengths, active):
        half = jnp.arange(active.shape[0]) < active.shape[0] // 2
        return orig(self, params, tokens, k_pool, v_pool, tables, lengths,
                    active & half)
    return step


def _token_altered(orig):
    def step(self, *args):
        logits, k, v, lengths = orig(self, *args)
        return jnp.roll(logits, 1, axis=-1), k, v, lengths
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.serving.engine import ServingEngine
    monkeypatch.setattr(ServingEngine, "_decode_fn",
                        fault(ServingEngine._decode_fn))
    out = _run()
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
