"""The Mamba2 + attention hybrid as a benchmark architecture
(``archs/mamba2_hybrid.py``): its sizes agree with the registry's, its
plain float32 reference agrees with the program, and the cell's whole
harness runs on it at the registry's smoke sizes on the CPU."""

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
from harness import correct, report, serve, spec, traffic, weights

CELL = "granite-4.0-h-micro.float.reason"
SMALL = {"arrivals": "poisson",
         "prompt": {"dist": "uniform", "min": 6, "max": 40},
         "output": {"dist": "uniform", "min": 4, "max": 12}}


@pytest.fixture(scope="module")
def smoke():
    return _paths.smoke_cell(CELL, traffic=SMALL, rate_per_s=120.0,
                             max_batch=4, page_size=4, max_seq_len=64)


def test_config_file_and_registry_give_equal_dims():
    cell = spec.load_cell(CELL)
    conf = spec.read_json(_paths.BENCH / "configs"
                          / "granite-4.0-h-micro.float.json")
    d = cell.arch.Dims.from_config(conf["config"])
    assert d == cell.arch.Dims.from_model(cell.cfg) == cell.dims
    assert (d.layers, d.attn_layers, d.mamba_layers) == (40, 4, 36)
    assert (d.ssm_heads, d.ssm_head_dim, d.d_state, d.d_inner) \
        == (64, 64, 128, 4096)
    assert (cell.max_batch, cell.max_seq_len) == (16, 1792)
    # the matrices the counts read are the weight tree's matrices
    flat = jax.tree_util.tree_flatten_with_path(
        cell.arch.shapes(d), is_leaf=weights._is_spec)[0]
    matrices = sum(int(np.prod(shape)) for path, (shape, _) in flat
                   if len(shape) >= 3
                   and path[-1].key not in ("conv_x_w", "conv_bc_w"))
    assert cell.arch.matmul_weights(d) == matrices + d.d_model * d.vocab


def test_top_level_keys_repeat_the_config_as_run():
    """The file's top level holds the published keys as run; the harness
    reads them from ``config``: the two copies agree key for key."""
    conf = spec.read_json(_paths.BENCH / "configs"
                          / "granite-4.0-h-micro.float.json")
    as_run = conf["config"]
    published = {k: v for k, v in as_run.items() if k != "torch_dtype"}
    assert {k: conf.get(k, "absent") for k in published} == published
    assert conf["max_position_embeddings"] == 1792
    assert list(conf["reduced"]) == ["max_position_embeddings"]


@pytest.mark.parametrize("key, value, match", [
    ("position_embedding_type", "rope", "position_embedding_type"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
    ("tie_word_embeddings", False, "tie_word_embeddings"),
])
def test_a_flag_the_reference_does_not_compute_raises(key, value, match):
    conf = spec.read_json(_paths.BENCH / "configs"
                          / "granite-4.0-h-micro.float.json")
    cell = spec.load_cell(CELL)
    with pytest.raises(ValueError, match=match):
        cell.arch.Dims.from_config({**conf["config"], key: value})


def test_dense_registry_entry_is_refused():
    from repro.configs import get_config
    cell = spec.load_cell(CELL)
    conf = spec.read_json(_paths.BENCH / "configs"
                          / "granite-4.0-h-micro.float.json")
    with pytest.raises(ValueError, match="no layer pattern"):
        cell.arch.check(get_config("internlm2-1.8b"), conf["config"])


def test_reference_agrees_with_the_program_and_not_with_its_control(smoke):
    """At float32 compute the model's forward and the plain reference are
    one computation; the fp8 control lies far outside their difference."""
    from repro.models import model as model_lib
    d = smoke.dims
    params = weights.make(smoke.arch.shapes(d), seed=7)
    toks = jnp.asarray(traffic.prompt_tokens(7, 1, 40, d.vocab))
    ref = np.asarray(smoke.arch.logits(params, toks, d=d))
    fwd, _ = model_lib.forward(params, smoke.cfg.replace(
        compute_dtype="float32"), toks[None])
    assert np.abs(np.asarray(fwd[0]) - ref).max() < 1e-5
    control = np.asarray(smoke.arch.logits(params, toks, d=d, fp8=True))
    assert np.abs(control - ref).max() > 100 * 1e-5


def _run(cell, seed=2**31 + 23):
    return report.run(spec.load_benchmark(), cell, seed, 0.1, False,
                      t_proc=time.perf_counter(), trace_root=None)


def test_sound_run_is_correct(smoke):
    """Warm-up, window, drain and check through ``ServingEngine.run``."""
    out = _run(smoke)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    assert set(out["metrics"]) == {"output_tok_s", "setup_s"}
    assert out["checks"]["max_logit_gap"]["value"] < 1e-2
    json.dumps(out)


def _state_unchanged(orig):
    def step(self, *args):
        logits, k, v, lengths, _ = orig(self, *args)
        return logits, k, v, lengths, args[-1]
    return step


def _token_altered(orig):
    def step(self, *args):
        logits, k, v, lengths, state = orig(self, *args)
        return jnp.roll(logits, 1, axis=-1), k, v, lengths, state
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_broken_timed_path_is_not_correct(smoke, monkeypatch, fault):
    from repro.serving.engine import ServingEngine
    monkeypatch.setattr(ServingEngine, "_hybrid_decode_fn",
                        fault(ServingEngine._hybrid_decode_fn))
    # sound runs at this size read ~1e-3, a stale state ~0.06
    cell = dataclasses.replace(smoke, limits={"max_logit_gap": 0.005})
    out = _run(cell)
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_reads_wider_than_the_program(smoke):
    served, params = serve.serve(smoke, 5, 0.1, t_proc=time.perf_counter())
    read = correct.readings(smoke, params, 5, served, control=True)
    assert read["requests"] >= 2
    assert read["control_gap"] > 3 * read["max_logit_gap"]


@pytest.mark.parametrize("length", [5, 64, 130])
def test_chunked_scan_is_the_recurrence(length):
    """The reference's chunked SSD against the recurrence run one position
    at a time in float64 (lengths inside one chunk, one whole chunk, and
    past two with a ragged end)."""
    from archs import mamba2_hybrid as arch
    rng = np.random.default_rng(length)
    x = rng.standard_normal((length, 4, 3))
    dt = np.log1p(np.exp(rng.standard_normal((length, 4))))
    a = -np.exp(0.1 * rng.standard_normal(4))
    b, c = rng.standard_normal((2, length, 4, 6))
    state, want = np.zeros((4, 6, 3)), []
    for t in range(length):
        state = (state * np.exp(dt[t] * a)[:, None, None]
                 + (dt[t][:, None] * b[t])[:, :, None] * x[t][:, None, :])
        want.append(np.einsum("hn,hnp->hp", c[t], state))
    got = arch.ssd(*(jnp.asarray(v, jnp.float32) for v in (x, dt, a, b, c)))
    np.testing.assert_allclose(np.asarray(got), np.stack(want), rtol=0,
                               atol=1e-4 * np.abs(want).max())
