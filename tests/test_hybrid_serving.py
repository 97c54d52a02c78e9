"""A layer pattern of Mamba2 and attention layers through ServingEngine.

granite-4.0-h-micro at its smoke size (two periods of mamba, mamba,
attention, mamba): the engine's bucketed prefill and paged decode against
the model's contiguous forward and against the benchmark's plain float32
reference (``bench/archs/mamba2_hybrid.py``), a padded prompt's SSM state
against the unpadded prompt's, and a slot's reuse against a fresh engine.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model as model_lib
from repro.serving import ServingEngine
from repro.serving.engine import _bucket
from repro.serving.paged_kv import PagedKVCache
from repro.serving.traffic import TrafficRequest

ARCH = "granite-4.0-h-micro"
#: engine against the contiguous forward and the reference, at float32
#: compute: different XLA programs (a padded (batch, bucket) prefill with
#: the chunked SSD scan, a one-token paged step, the reference's own
#: chunked float32 scan), so float32 reassociation is their only licensed
#: difference; the logits here reach ~0.3 and differ by ~1e-7.
LOGIT_TOL = 1e-5
#: the same for the SSM state and conv tails of a padded prompt
STATE_TOL = 1e-5


@pytest.fixture(scope="module")
def cfg():
    return configs.get_smoke_config(ARCH)


@pytest.fixture(scope="module")
def cfg32(cfg):
    return cfg.replace(compute_dtype="float32")


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(3))


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _engine_logits(engine, prompts, tokens):
    """Per prompt, the engine's logits at each position its ``tokens``
    continue from: the prefill's last row, then one paged decode step per
    token, all prompts decoded together (ragged lengths)."""
    b = engine.max_batch
    cache = PagedKVCache(
        num_layers=engine._kv_layers, num_kv_heads=engine.cfg.num_kv_heads,
        head_dim=engine.cfg.resolved_head_dim, num_pages=engine.num_pages,
        page_size=engine.page_size, max_seq_len=engine.max_seq_len,
        slot_state=engine._zero_state())
    btables = np.zeros((b, cache.max_blocks), np.int32)
    out = [[] for _ in prompts]
    with jax.set_mesh(engine._mesh):
        rows = engine._prefill_padded(prompts)
        for slot, (p, (last, k_call, v_call, row, state)) in enumerate(
                zip(prompts, rows)):
            cache.allocate(slot, len(p) + len(tokens[slot]))
            ids = np.zeros(-(-k_call.shape[2] // engine.page_size), np.int32)
            n = cache.pages_needed(len(p))
            ids[:n] = cache.block_tables[slot][:n]
            cache.sync_pools(*engine._write_prompt(
                cache.k_pool, cache.v_pool, k_call, v_call, row, len(p),
                ids))
            cache.sync_state(engine._write_state(cache.slot_state, state,
                                                 row, slot))
            btables[slot] = cache.block_table_row(slot)
            out[slot].append(np.asarray(last, np.float32))
        lengths = np.array([len(p) for p in prompts]
                           + [0] * (b - len(prompts)), np.int32)
        active = np.arange(b) < len(prompts)
        steps = max(len(t) for t in tokens) - 1
        d_len = jnp.asarray(lengths)
        for i in range(steps):
            tok = np.zeros((b, 1), np.int32)
            for slot, t in enumerate(tokens):
                tok[slot, 0] = t[min(i, len(t) - 1)]
            lg, k_pool, v_pool, d_len, state = engine._decode(
                engine._exec_params, jnp.asarray(tok), cache.k_pool,
                cache.v_pool, jnp.asarray(btables), d_len,
                jnp.asarray(active), cache.slot_state)
            cache.sync_pools(k_pool, v_pool)
            cache.sync_state(state)
            for slot, t in enumerate(tokens):
                if i < len(t) - 1:
                    out[slot].append(np.asarray(lg[slot, 0], np.float32))
    return [np.stack(o) for o in out]


def test_engine_logits_match_contiguous_forward_and_reference(cfg32, params):
    sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "bench")]
    try:
        from archs import mamba2_hybrid as ref_lib
    finally:
        del sys.path[:1]
    d = ref_lib.Dims.from_model(cfg32)
    engine = ServingEngine(cfg32, params, max_batch=3, page_size=4,
                           max_seq_len=32)
    prompts = _prompts(cfg32.vocab_size, [5, 11, 8])
    tokens = _prompts(cfg32.vocab_size, [6, 4, 7], seed=1)
    got = _engine_logits(engine, prompts, tokens)
    for p, t, lg in zip(prompts, tokens, got):
        seq = np.concatenate([p, t[:-1]])
        rows = np.arange(len(p) - 1, len(seq))
        fwd, _ = model_lib.forward(params, cfg32, jnp.asarray(seq)[None])
        ref = ref_lib.logits(params, jnp.asarray(seq), d=d)
        np.testing.assert_allclose(lg, np.asarray(fwd[0])[rows],
                                   rtol=0, atol=LOGIT_TOL)
        np.testing.assert_allclose(lg, np.asarray(ref)[rows],
                                   rtol=0, atol=LOGIT_TOL)
        assert np.abs(lg).max() > 100 * LOGIT_TOL


@pytest.mark.parametrize("length", [3, 6, 13])
def test_padded_prompt_leaves_the_unpadded_state(cfg32, params, length):
    """The engine's prefill pads the prompt to ``(max_batch, bucket)``; the
    row's SSM states and conv tails are those of the prompt alone."""
    engine = ServingEngine(cfg32, params, max_batch=2, page_size=4,
                           max_seq_len=32)
    (p,) = _prompts(cfg32.vocab_size, [length], seed=length)
    with jax.set_mesh(engine._mesh):
        (_, _, _, row, padded), = engine._prefill_padded([p])
        caches = model_lib.init_caches(cfg32, 1, length, dtype=jnp.float32)
        _, alone = model_lib.prefill(params, cfg32, jnp.asarray(p)[None],
                                     caches=caches)
    assert _bucket(length) > length
    for key in ("state", "conv_x", "conv_bc"):
        np.testing.assert_allclose(np.asarray(padded[key][:, row]),
                                   np.asarray(alone["ssm"][key][:, 0]),
                                   rtol=0, atol=STATE_TOL, err_msg=key)
    # the padded positions really were there: past the length, the state
    # would have moved on
    caches = model_lib.init_caches(cfg32, 1, _bucket(length),
                                   dtype=jnp.float32)
    whole = np.zeros(_bucket(length), np.int32)
    whole[:length] = p
    _, full = model_lib.prefill(params, cfg32, jnp.asarray(whole)[None],
                                caches=caches)
    assert np.abs(np.asarray(full["ssm"]["state"][:, 0])
                  - np.asarray(padded["state"][:, row])).max() > 1e-3


def test_reused_slot_serves_what_a_fresh_engine_serves(cfg, params):
    """Two slots, six requests: every slot is evicted and re-admitted, and
    each request's tokens equal those of an engine that served it alone."""
    kw = dict(max_batch=2, page_size=4, max_seq_len=48)
    trace = tuple(TrafficRequest(i, i, 5 + 3 * i, 4 + (i % 3))
                  for i in range(6))
    report = ServingEngine(cfg, params, **kw).run(trace, "continuous")
    admitted = [r for _, what, r in report.events if what == "admit"]
    assert len(admitted) == 6
    for req in trace:
        alone = ServingEngine(cfg, params, **kw).run(
            (TrafficRequest(req.req_id, 0, req.prompt_len, req.output_len),),
            "continuous")
        assert alone.request_tokens[req.req_id] \
            == report.request_tokens[req.req_id]


def test_engine_keeps_pages_for_attention_layers_only(cfg, params):
    engine = ServingEngine(cfg, params, max_batch=2, page_size=4,
                           max_seq_len=16)
    assert engine._kv_layers == cfg.layer_types.count("attention") == 2
    state = engine._zero_state()
    n = cfg.layer_types.count("mamba")
    assert state["state"].shape[:2] == (n, 2)
    assert engine._slot_state_bytes == sum(
        a.size // 2 * 4 for a in jax.tree_util.tree_leaves(state))


def test_engine_refuses_other_hybrids(params):
    zamba = configs.get_smoke_config("zamba2-1.2b")
    with pytest.raises(ValueError, match="layer patterns"):
        ServingEngine(zamba, params)


def test_state_write_span_and_ssm_scopes(cfg, params):
    """Each admission records one ``ssm.write_state`` span counting the
    slot's bytes; the decode step's recurrence lies under ``ssm_step``,
    the prefill's under ``ssd_scan``."""
    import re

    from repro.serving.spans import SpanRecorder
    engine = ServingEngine(cfg, params, max_batch=2, page_size=4,
                           max_seq_len=32)
    rec = SpanRecorder()
    trace = tuple(TrafficRequest(i, i, 6 + i, 3) for i in range(3))
    engine.run(trace, "continuous", spans=rec)
    writes = [s for s in rec.dump()["spans"]
              if s["name"] == "ssm.write_state"]
    assert len(writes) == 3
    assert all(w["counts"]["slots"] == 1
               and w["counts"]["bytes"] == engine._slot_state_bytes
               for w in writes)
    b, state = engine.max_batch, engine._zero_state()
    pool = jnp.zeros((engine._kv_layers, engine.num_pages, 4,
                      cfg.num_kv_heads, cfg.resolved_head_dim))
    with jax.set_mesh(engine._mesh):
        decode = engine._decode.lower(
            engine._exec_params, jnp.zeros((b, 1), jnp.int32), pool, pool,
            jnp.zeros((b, 8), jnp.int32), jnp.zeros((b,), jnp.int32),
            jnp.zeros((b,), bool), state).compile().as_text()
        prefill = jax.jit(lambda p, t: model_lib.prefill(
            p, cfg, t, caches=model_lib.init_caches(cfg, 1, 8))).lower(
            params, jnp.zeros((1, 8), jnp.int32)).compile().as_text()
    decode_ops = set(re.findall(r'op_name="([^"]*)"', decode))
    for scope in ("layers/mamba/ssm/ssm_step/", "layers/mamba/ssm/w_z/",
                  "layers/attention/attn/page_walk/",
                  "layers/mamba/mlp/w_up/"):
        assert any(scope in n for n in decode_ops), scope
    assert "ssd_scan" not in decode and "ssd_scan/" in prefill
