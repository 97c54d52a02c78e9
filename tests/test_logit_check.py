"""The serving path's decode logits against the float32 reference.

``repro.models.reference`` restates the dense GQA decoder in plain
``jax.numpy``; ``repro.serving.logit_check`` replays served requests through
the engine's prefill + paged decode and holds the logits to it.  Pinned
here at CPU size: the reference agrees with the model's own forward pass
when both compute in float32, the check passes on served bf16 engines, and
it fails when one part of the model runs below the precision the config
states (float8 weights).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as model_lib
from repro.models.reference import reference_logits
from repro.serving import ServingEngine, TrafficConfig, generate_trace
from repro.serving.logit_check import (BF16_REL_TOL, F32_REL_TOL,
                                       check_decode_logits, logit_tolerance)

ENGINE_KW = dict(max_batch=4, page_size=8, max_seq_len=64)


@pytest.fixture(scope="module")
def cfg():
    return configs.get_smoke_config("internlm2-1.8b")


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def trace():
    return generate_trace(TrafficConfig(num_requests=4, arrival_rate=1.0,
                                        seed=0))


@pytest.fixture(scope="module")
def served(cfg, params, trace):
    """(prompts, emitted tokens) of one continuous run of the bf16 engine."""
    engine = ServingEngine(cfg, params, **ENGINE_KW)
    report = engine.run(trace)
    return ([engine.prompt_tokens(r) for r in trace],
            [report.request_tokens[r.req_id] for r in trace])


def _f8(w):
    return w.astype(jnp.float8_e4m3fn).astype(w.dtype)


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "llama3-8b"])
def test_reference_matches_model_forward_at_f32(arch):
    cfg = configs.get_smoke_config(arch).replace(compute_dtype="float32")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(1))
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, 24)
    ref = np.asarray(reference_logits(params, cfg, tokens))
    got, _ = model_lib.forward(params, cfg, jnp.asarray(tokens)[None])
    got = np.asarray(got[0], np.float32)
    assert ref.shape == got.shape == (24, cfg.vocab_size)
    rel = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    assert rel.max() < F32_REL_TOL


def test_tolerance_follows_compute_dtype(cfg):
    assert logit_tolerance(cfg) == BF16_REL_TOL
    assert logit_tolerance(cfg.replace(compute_dtype="float32")) \
        == F32_REL_TOL


@pytest.mark.parametrize("compute_dtype", ["bfloat16", "float32"])
def test_served_engine_passes(cfg, params, served, compute_dtype):
    engine = ServingEngine(cfg.replace(compute_dtype=compute_dtype), params,
                           **ENGINE_KW)
    check = check_decode_logits(engine, *served)
    assert check.rows == sum(len(o) for o in served[1])
    assert check.ok, check.line()
    if compute_dtype == "bfloat16":
        assert check.replay_agreement == 1.0


@pytest.mark.parametrize("part", ["mlp", "attn"])
def test_float8_weights_fail(cfg, params, served, part):
    """A model part computed below bf16 precision must not pass."""
    low = dict(params)
    low["layers"] = dict(params["layers"])
    low["layers"][part] = jax.tree_util.tree_map(_f8, params["layers"][part])
    engine = ServingEngine(cfg, low, **ENGINE_KW)
    engine.params = params          # the reference keeps the true weights
    check = check_decode_logits(engine, *served)
    assert not check.ok, check.line()


def test_rejects_quantized_engine(cfg, params, served):
    engine = ServingEngine(cfg, params, backend="tubgemm", bits=4,
                           **ENGINE_KW)
    with pytest.raises(ValueError, match="float model"):
        check_decode_logits(engine, *served)
