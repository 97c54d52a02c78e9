"""Per-architecture smoke tests: reduced configs, one forward + one train
step on CPU, asserting output shapes and no NaNs (assignment requirement)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import configs
from repro.models import model as M
from repro.models import moe as moe_lib
from repro.optim import AdamWConfig, adamw_init, adamw_update

ARCHS = list(configs.ARCH_IDS)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def arch_setup(key):
    """(cfg, params) per arch, shared by both smoke tests (params are
    immutable jax trees; init is seconds per arch and was paid twice)."""
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = configs.get_smoke_config(arch)
            cache[arch] = (cfg, M.init_params(cfg, key))
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch, arch_setup, rng):
    cfg, params = arch_setup(arch)
    B, Sq = 2, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, Sq)), jnp.int32)
    embeds = None
    if cfg.frontend_stub:
        embeds = jnp.asarray(rng.normal(0, 1, (B, Sq, cfg.d_model)), jnp.float32)

    logits, aux = M.forward(params, cfg, None if cfg.frontend_stub else toks,
                            embeds=embeds)
    assert logits.shape == (B, Sq, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits))), f"{arch}: NaN logits"

    # one full train step (loss -> grads -> AdamW update)
    def loss_of(p):
        return M.loss_fn(p, cfg, None if cfg.frontend_stub else toks[:, :-1],
                         toks[:, 1:],
                         embeds=None if embeds is None else embeds[:, :-1])[0]

    # jit once and reuse: un-jitted value_and_grad re-traces op-by-op on
    # every call, which used to dominate the suite's wall clock
    val_grad = jax.jit(jax.value_and_grad(loss_of))
    loss, grads = val_grad(params)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params, opt_cfg)
    new_params, new_opt, metrics = adamw_update(grads, opt, params, opt_cfg, 1e-3)
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    moved = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(new_params)))
    assert moved, f"{arch}: update was a no-op"
    # loss must decrease after a few steps on the same batch (sanity)
    p, o = new_params, new_opt
    for _ in range(3):
        l2, g = val_grad(p)
        p, o, _ = adamw_update(g, o, p, opt_cfg, 1e-3)
    assert float(val_grad(p)[0]) < float(loss), f"{arch}: loss not decreasing"


class _Routing:
    """Each MoE layer's expert choices and capacity drops, per token, in
    the calls made inside ``run``.

    Routing is not a continuous function of the hidden state.  Two paths
    that agree to rounding (a cached decode and a whole forward) can send
    a token whose two best router scores nearly tie to another expert, and
    an expert's capacity scales with the tokens in the call, so a forward
    over more tokens may drop a token a decode step keeps.  Either changes
    that token's output, and its row's later positions, by design and not
    by a fault of the cache path.  ``moe._local_expert_pass`` is wrapped
    to read both.
    """

    def __init__(self, monkeypatch):
        self.layers = []
        inner = moe_lib._local_expert_pass

        def wrapped(x_flat, topk_idx, topk_w, wg, *rest):
            cfg = rest[-2]
            n_exp = cfg.moe.num_experts
            cap = moe_lib._capacity(x_flat.shape[0], cfg)
            # (E, T): each expert's weight on each token, 0 if not routed
            w_tok = jnp.sum(jnp.where(
                topk_idx[None] == jnp.arange(n_exp)[:, None, None],
                topk_w[None], 0.0), axis=-1)
            _, sel = lax.top_k(w_tok, cap)      # the pass's own selection
            kept = jnp.zeros(w_tok.shape, bool).at[
                jnp.arange(n_exp)[:, None], sel].set(True)
            jax.debug.callback(
                lambda i, d: self.layers.append(
                    (np.sort(np.asarray(i), axis=-1), np.asarray(d))),
                topk_idx, jnp.any((w_tok > 0) & ~kept, axis=0), ordered=True)
            return inner(x_flat, topk_idx, topk_w, wg, *rest)

        monkeypatch.setattr(moe_lib, "_local_expert_pass", wrapped)

    def run(self, call, batch):
        """``call()``'s result, and per MoE layer in order its (batch, S,
        K) expert choices and (batch, S) drops."""
        self.layers.clear()
        out = call()
        jax.effects_barrier()
        return out, [(i.reshape(batch, -1, i.shape[-1]), d.reshape(batch, -1))
                     for i, d in self.layers]


def _unlike(a, b, at=0):
    """Rows in which call ``a``'s routing differs from call ``b``'s at the
    positions ``a`` holds (from ``at`` in ``b``), or either dropped a
    token."""
    assert len(a) == len(b)
    rows = np.zeros(a[0][0].shape[0] if a else 0, bool)
    for (ia, da), (ib, db) in zip(a, b):
        rows = rows | (ia != ib[:, at:at + ia.shape[1]]).any(axis=(1, 2)) \
            | da.any(axis=1) | db.any(axis=1)
    return rows


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_consistency(arch, arch_setup, monkeypatch):
    """prefill+decode logits match full forward (bf16 tolerance), in every
    row whose MoE routing the two paths share (``_Routing``)."""
    cfg, params = arch_setup(arch)
    if cfg.frontend_stub:
        pytest.skip("frontend-stub archs serve embeddings; covered elsewhere")
    # inputs of the test's own: tests added elsewhere cannot shift them
    rng = np.random.default_rng(list(arch.encode()))
    routing = _Routing(monkeypatch)
    # four rows, so a row left out for its routing leaves others compared
    B, Sq = 4, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, Sq)), jnp.int32)
    (logits, _), r_fwd = routing.run(lambda: M.forward(params, cfg, toks), B)
    caches = M.init_caches(cfg, B, Sq + 4, dtype=jnp.float32)
    (plog, caches), r_pre = routing.run(
        lambda: M.prefill(params, cfg, toks, caches=caches), B)
    keep = ~_unlike(r_pre, r_fwd) if cfg.is_moe else np.ones(B, bool)
    assert keep.any(), "every row's routing differs: nothing compared"
    np.testing.assert_allclose(np.asarray(plog[keep, -1], np.float32),
                               np.asarray(logits[keep, -1], np.float32),
                               rtol=0.15, atol=0.15)
    (dlog, _), r_dec = routing.run(
        lambda: M.decode_step(params, cfg, toks[:, -1:], caches=caches,
                              cache_pos=Sq), B)
    toks2 = jnp.concatenate([toks, toks[:, -1:]], axis=1)
    (ref2, _), r_fwd2 = routing.run(lambda: M.forward(params, cfg, toks2), B)
    if cfg.is_moe:
        keep = ~(_unlike(r_pre, r_fwd2) | _unlike(r_dec, r_fwd2, at=Sq))
        assert keep.any(), "every row's routing differs: nothing compared"
    np.testing.assert_allclose(np.asarray(dlog[keep, 0], np.float32),
                               np.asarray(ref2[keep, -1], np.float32),
                               rtol=0.15, atol=0.15)


def test_full_config_param_counts():
    """Full configs land near published parameter counts (defs only)."""
    from repro.models.common import ParamDef
    expect = {"llama3-8b": 8.0e9, "gemma-7b": 8.5e9, "phi3-mini-3.8b": 3.8e9,
              "internlm2-1.8b": 1.9e9, "zamba2-1.2b": 1.2e9,
              "rwkv6-3b": 3.1e9, "phi3.5-moe-42b-a6.6b": 41.9e9,
              "chameleon-34b": 34.3e9, "musicgen-medium": 1.4e9,
              "deepseek-v3-671b": 700e9, "granite-4.0-h-micro": 3.19e9}
    for arch, want in expect.items():
        cfg = configs.get_config(arch)
        defs = M.model_defs(cfg)
        tot = 0
        for d in jax.tree_util.tree_leaves(
                defs, is_leaf=lambda x: isinstance(x, ParamDef)):
            sz = 1
            for s in d.shape:
                sz *= s
            tot += sz
        assert tot == pytest.approx(want, rel=0.12), f"{arch}: {tot/1e9:.2f}B"


def test_long_500k_applicability():
    """Assignment: long_500k runs only for sub-quadratic archs."""
    runnable = {a for a, s in configs.cells() if s == "long_500k"}
    assert runnable == {"zamba2-1.2b", "rwkv6-3b", "granite-4.0-h-micro"}
    assert len(configs.cells(include_skipped=True)) == 44
    assert len(configs.cells()) == 36
