"""Layer blocks and scan-over-layers stacking for every architecture family.

Families:
  dense / moe / audio / vlm : pre-norm attention + (MLP | MoE) blocks, scanned
  ssm (cfg.ssm set)         : Mamba2 blocks, scanned
  ssm (cfg.rwkv set)        : RWKV6 blocks, scanned
  hybrid                    : Mamba2 backbone with a *shared* attention+MLP
                              block applied every ``hybrid_attn_every`` layers
                              (Zamba2-style); grouped scan so the shared block
                              lowers exactly once per application site.
  hybrid (cfg.layer_types)  : the published per-layer pattern of Mamba2 and
                              attention mixers, each layer with weights of
                              its own and an MLP after its mixer
                              (granite-4.0-h); one scan per run of a kind
                              inside a scan over the pattern's periods.

All per-layer parameters are stacked with a leading ``layers`` axis and
consumed via ``lax.scan`` — keeping HLO size (and CPU dry-run compile time)
independent of depth.  ``jax.checkpoint`` wraps the body when cfg.remat.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.backends.runtime import site_scope
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.common import ParamDef, rmsnorm, shard
from repro.models.config import ModelConfig
from repro.models.mlp import mlp_defs, mlp_fwd

__all__ = [
    "layer_defs", "stacked_layer_defs", "shared_attn_defs",
    "stack_fwd", "init_layer_caches", "hybrid_counts",
    "pattern_layer_defs", "layer_runs", "pattern_fwd", "CACHE_KEY",
]

#: the caches of each kind of a layer pattern live under the key the
#: family stacks use ("ssm", "attn")
CACHE_KEY = {"mamba": "ssm", "attention": "attn"}


# ---------------------------------------------------------------------------
# Definitions
# ---------------------------------------------------------------------------

def layer_defs(cfg: ModelConfig) -> dict:
    """ParamDefs for ONE layer of the given family."""
    if cfg.family == "hybrid" or (cfg.family == "ssm" and cfg.ssm is not None):
        return {"ln": ParamDef((cfg.d_model,), ("embed",), init="ones"),
                "ssm": ssm_lib.ssm_defs(cfg)}
    if cfg.family == "ssm" and cfg.rwkv is not None:
        return rwkv_lib.rwkv_defs(cfg)
    # attention transformer
    defs = {
        "ln1": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn_lib.attention_defs(cfg),
        "ln2": ParamDef((cfg.d_model,), ("embed",), init="ones"),
    }
    if cfg.is_moe:
        defs["moe"] = moe_lib.moe_defs(cfg)
    else:
        defs["mlp"] = mlp_defs(cfg)
    return defs


def shared_attn_defs(cfg: ModelConfig) -> dict:
    """Zamba2 shared attention+MLP block (one copy, applied at many sites)."""
    return {
        "ln1": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "attn": attn_lib.gqa_defs(cfg),
        "ln2": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "mlp": mlp_defs(cfg),
    }


def _stack_def(d: ParamDef, n: int) -> ParamDef:
    return dataclasses.replace(
        d, shape=(n, *d.shape), logical=("layers", *d.logical),
        fan_in_axes=tuple(a + 1 for a in d.fan_in_axes))


def pattern_layer_defs(cfg: ModelConfig) -> dict:
    """ParamDefs for ONE layer of each kind ``cfg.layer_types`` names."""
    def layer(mixer_key, mixer):
        return {"ln1": ParamDef((cfg.d_model,), ("embed",), init="ones"),
                mixer_key: mixer,
                "ln2": ParamDef((cfg.d_model,), ("embed",), init="ones"),
                "mlp": mlp_defs(cfg)}
    kinds = {"mamba": lambda: layer("ssm", ssm_lib.ssm_defs(cfg)),
             "attention": lambda: layer("attn", attn_lib.gqa_defs(cfg))}
    return {k: make() for k, make in kinds.items() if k in cfg.layer_types}


def stacked_layer_defs(cfg: ModelConfig, n: int | None = None) -> dict:
    if cfg.layer_types is not None:
        return {kind: _stacked(defs, cfg.layer_types.count(kind))
                for kind, defs in pattern_layer_defs(cfg).items()}
    n = cfg.num_layers if n is None else n
    return _stacked(layer_defs(cfg), n)


def _stacked(defs: dict, n: int) -> dict:
    return jax.tree_util.tree_map(
        lambda d: _stack_def(d, n), defs,
        is_leaf=lambda x: isinstance(x, ParamDef))


def hybrid_counts(cfg: ModelConfig) -> tuple[int, int, int]:
    """(n_groups, group_size, remainder) for the hybrid grouped scan."""
    every = cfg.hybrid_attn_every
    return cfg.num_layers // every, every, cfg.num_layers % every


def layer_runs(cfg: ModelConfig) -> tuple[tuple[tuple[str, int, int], ...],
                                          int]:
    """``cfg.layer_types`` as its shortest period and the period's repeats.

    The period is given as runs of one kind, ``(kind, lo, hi)``: the run
    holds that kind's layers ``lo..hi-1`` counted within one period.
    Granite-4.0-h's 40 layers are 4 periods of ``(mamba, 0, 5),
    (attention, 0, 1), (mamba, 5, 9)``.
    """
    types = tuple(cfg.layer_types)
    n = len(types)
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and types == types[:p] * (n // p))
    runs: list[tuple[str, int, int]] = []
    seen: dict[str, int] = {}
    for kind in types[:period]:
        lo = seen.get(kind, 0)
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1], lo + 1)
        else:
            runs.append((kind, lo, lo + 1))
        seen[kind] = lo + 1
    return tuple(runs), n // period


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _transformer_block(layer_params, x, cfg: ModelConfig, *, positions,
                       cache, cache_pos, kv_valid_len):
    h = rmsnorm(layer_params["ln1"], x, cfg.rms_eps)
    with site_scope("attn"):
        attn_out, new_cache = attn_lib.attention_fwd(
            layer_params["attn"], h, cfg, positions=positions, cache=cache,
            cache_pos=cache_pos, kv_valid_len=kv_valid_len)
    x = x + attn_out
    h = rmsnorm(layer_params["ln2"], x, cfg.rms_eps)
    if cfg.is_moe:
        with site_scope("moe"):
            out, aux = moe_lib.moe_fwd(layer_params["moe"], h, cfg)
    else:
        with site_scope("mlp"):
            out, aux = mlp_fwd(layer_params["mlp"], h, cfg), jnp.float32(0.0)
    return x + out, new_cache, aux


def _mamba_block(layer_params, x, cfg: ModelConfig, *, cache):
    h = rmsnorm(layer_params["ln"], x, cfg.rms_eps)
    with site_scope("ssm"):
        out, new_cache = ssm_lib.ssm_fwd(layer_params["ssm"], h, cfg,
                                         cache=cache)
    return x + out, new_cache


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat:
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return fn


def _residual(out, cfg: ModelConfig):
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return out


def pattern_fwd(layers: dict, x: jax.Array, cfg: ModelConfig, mixers: dict,
                caches: dict | None = None):
    """``x`` through the layers of ``cfg.layer_types``.

    Each layer: ``x + mixer(RMSNorm(x))``, then ``x + MLP(RMSNorm(x))``,
    both outputs scaled by ``cfg.residual_multiplier``.  ``mixers[kind](lp,
    h, cache) -> (out, new_cache)`` runs one layer's mixer on its
    parameters and on its slice of ``caches[CACHE_KEY[kind]]`` (None
    without caches).  ``layers[kind]`` and the caches stack that kind's
    layers.  One scan over the periods of ``layer_runs``, inside it one
    scan per run over the layers' indices; each layer's weights and cache
    slice are read out of the whole stacks by index, the caches ride in the
    carry and each layer's slice is written back by its index, so a donated
    cache is updated in place; a Mamba2 layer's slice is read and written
    back under ``ssm.state_scope``, with its update.
    Returns ``(x, new_caches)``.
    """
    runs, reps = layer_runs(cfg)
    per = {kind: hi for kind, _, hi in runs}

    def cache_scope(kind):
        if kind == "mamba":
            return ssm_lib.state_scope(x.shape[1] == 1)
        return contextlib.nullcontext()

    def take(tree, i):
        return jax.tree_util.tree_map(
            lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)

    def layer(kind, carry, i):
        # the layer's weights and cache slice are indexed out of the whole
        # stacks: a scan over slices of a slice would copy the weights
        xc, c = carry
        key = CACHE_KEY[kind]
        lp = take(layers[kind], i)
        with cache_scope(kind):
            lc = None if c is None else take(c[key], i)
        with site_scope("layers"), site_scope(kind):
            h = rmsnorm(lp["ln1"], xc, cfg.rms_eps)
            out, nc = mixers[kind](lp, h, lc)
            xc = xc + _residual(out, cfg)
            h = rmsnorm(lp["ln2"], xc, cfg.rms_eps)
            with site_scope("mlp"):
                xc = xc + _residual(mlp_fwd(lp["mlp"], h, cfg), cfg)
        if c is not None:
            with cache_scope(kind):
                c = {**c, key: jax.tree_util.tree_map(
                    lambda a, n: lax.dynamic_update_index_in_dim(
                        a, n.astype(a.dtype), i, 0), c[key], nc)}
        return xc, c

    def period(carry, p):
        for kind, lo, hi in runs:
            def step(carry, i, kind=kind):
                return layer(kind, carry, i), None
            if caches is None:
                step = _maybe_remat(step, cfg)
            carry, _ = lax.scan(step, carry, p * per[kind] + jnp.arange(lo, hi))
        return carry, None

    (x, caches), _ = lax.scan(period, (x, caches), jnp.arange(reps))
    return x, caches


def _pattern_stack_fwd(params, x, cfg, *, positions, caches, cache_pos,
                       kv_valid_len, lengths):
    def mamba(lp, h, cache):
        with site_scope("ssm"):
            return ssm_lib.ssm_fwd(lp["ssm"], h, cfg, cache=cache,
                                   lengths=lengths)

    def attention(lp, h, cache):
        with site_scope("attn"):
            return attn_lib.attention_fwd(
                lp["attn"], h, cfg, positions=positions, cache=cache,
                cache_pos=cache_pos, kv_valid_len=kv_valid_len)

    x, new = pattern_fwd(params["layers"], x, cfg,
                         {"mamba": mamba, "attention": attention}, caches)
    return x, new, jnp.float32(0.0)


def stack_fwd(params: dict, x: jax.Array, cfg: ModelConfig, *,
              positions, caches: dict | None = None, cache_pos=0,
              kv_valid_len=None, lengths=None):
    """Run the full layer stack.  Returns (x, new_caches, aux_loss).

    ``params`` holds "layers" (stacked) and, for hybrid, "shared" +
    "layers_tail".  ``caches`` mirrors that structure with stacked caches.
    ``lengths`` (B,), a layer pattern's prefill only: each row's true
    length, at which its SSM state and conv tails are taken.
    """
    if cfg.layer_types is not None:
        return _pattern_stack_fwd(params, x, cfg, positions=positions,
                                  caches=caches, cache_pos=cache_pos,
                                  kv_valid_len=kv_valid_len, lengths=lengths)
    if cfg.family == "hybrid":
        return _hybrid_fwd(params, x, cfg, positions=positions, caches=caches,
                           cache_pos=cache_pos, kv_valid_len=kv_valid_len)
    if cfg.family == "ssm" and cfg.rwkv is not None:
        def body(carry, xs):
            xc = carry
            lp, lc = xs
            with site_scope("layers"):
                out, nc = rwkv_lib.rwkv_block_fwd(lp, xc, cfg, cache=lc)
            return out, nc
        body = _maybe_remat(body, cfg)
        lc = caches["rwkv"] if caches is not None else None
        x, new = _scan_layers(body, x, params["layers"], lc)
        return x, ({"rwkv": new} if caches is not None else None), jnp.float32(0.0)

    if cfg.family == "ssm":
        def body(carry, xs):
            xc = carry
            lp, lc = xs
            with site_scope("layers"):
                out, nc = _mamba_block(lp, xc, cfg, cache=lc)
            return out, nc
        body = _maybe_remat(body, cfg)
        lc = caches["ssm"] if caches is not None else None
        x, new = _scan_layers(body, x, params["layers"], lc)
        return x, ({"ssm": new} if caches is not None else None), jnp.float32(0.0)

    # attention transformer (dense / moe / audio / vlm)
    def body(carry, xs):
        xc, aux = carry
        lp, lc = xs
        with site_scope("layers"):
            out, nc, a = _transformer_block(lp, xc, cfg, positions=positions,
                                            cache=lc, cache_pos=cache_pos,
                                            kv_valid_len=kv_valid_len)
        return (out, aux + a), nc
    body = _maybe_remat(body, cfg)
    lc = caches["attn"] if caches is not None else None
    (x, aux), new = _scan_layers(body, (x, jnp.float32(0.0)), params["layers"], lc)
    return x, ({"attn": new} if caches is not None else None), aux


def _scan_layers(body, carry0, stacked_params, stacked_caches):
    if stacked_caches is None:
        carry, _ = lax.scan(lambda c, p: (body(c, (p, None))[0], None),
                            carry0, stacked_params)
        return carry, None
    return lax.scan(body, carry0, (stacked_params, stacked_caches))


def _hybrid_fwd(params, x, cfg, *, positions, caches, cache_pos, kv_valid_len):
    """Grouped scan: [group_size mamba layers + shared attn] x n_groups + tail."""
    n_groups, gsize, rem = hybrid_counts(cfg)
    shared = params["shared"]
    has_cache = caches is not None

    def mamba_body(xc, xs):
        lp, lc = xs
        with site_scope("layers"):
            out, nc = _mamba_block(lp, xc, cfg, cache=lc)
        return out, nc
    mamba_body = _maybe_remat(mamba_body, cfg)

    def group_body(xc, xs):
        grp_params, grp_cache, attn_cache = xs
        if has_cache:
            xc, new_ssm = lax.scan(mamba_body, xc, (grp_params, grp_cache))
        else:
            xc, new_ssm = _scan_layers(mamba_body, xc, grp_params, None)
        h = rmsnorm(shared["ln1"], xc, cfg.rms_eps)
        with site_scope("shared"), site_scope("attn"):
            attn_out, new_attn = attn_lib.attention_fwd(
                shared["attn"], h, cfg, positions=positions, cache=attn_cache,
                cache_pos=cache_pos, kv_valid_len=kv_valid_len)
        xc = xc + attn_out
        h = rmsnorm(shared["ln2"], xc, cfg.rms_eps)
        with site_scope("shared"), site_scope("mlp"):
            xc = xc + mlp_fwd(shared["mlp"], h, cfg)
        return xc, (new_ssm, new_attn)

    # reshape stacked (L, ...) params into (n_groups, gsize, ...)
    main = jax.tree_util.tree_map(
        lambda a: a[: n_groups * gsize].reshape(n_groups, gsize, *a.shape[1:]),
        params["layers"])
    tail = jax.tree_util.tree_map(lambda a: a[n_groups * gsize:], params["layers"])

    if has_cache:
        ssm_c = jax.tree_util.tree_map(
            lambda a: a[: n_groups * gsize].reshape(n_groups, gsize, *a.shape[1:]),
            caches["ssm"])
        ssm_tail_c = jax.tree_util.tree_map(lambda a: a[n_groups * gsize:],
                                            caches["ssm"])
        attn_c = caches["attn"]
        x, (new_ssm_g, new_attn) = lax.scan(group_body, x, (main, ssm_c, attn_c))
        x, new_tail = lax.scan(mamba_body, x, (tail, ssm_tail_c)) if rem else (x, None)
        new_ssm = jax.tree_util.tree_map(
            lambda g: g.reshape(-1, *g.shape[2:]), new_ssm_g)
        if rem:
            new_ssm = jax.tree_util.tree_map(
                lambda g, t: jnp.concatenate([g, t], axis=0), new_ssm, new_tail)
        return x, {"ssm": new_ssm, "attn": new_attn}, jnp.float32(0.0)

    x, _ = lax.scan(lambda c, p: (group_body(c, (p, None, None))[0], None), x, main)
    if rem:
        x = lax.scan(lambda c, p: (mamba_body(c, (p, None))[0], None), x, tail)[0]
    return x, None, jnp.float32(0.0)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_layer_caches(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=jnp.bfloat16) -> dict:
    """Stacked caches matching stack_fwd's expectations."""
    def stack(make_one, n):
        one = make_one()
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n, *a.shape)).copy(), one)

    if cfg.layer_types is not None:
        return {
            CACHE_KEY["mamba"]: stack(
                lambda: ssm_lib.init_ssm_cache(cfg, batch, dtype),
                cfg.layer_types.count("mamba")),
            CACHE_KEY["attention"]: stack(
                lambda: attn_lib.init_kv_cache(cfg, batch, max_len, dtype),
                cfg.layer_types.count("attention")),
        }
    if cfg.family == "hybrid":
        n_groups, _, _ = hybrid_counts(cfg)
        return {
            "ssm": stack(lambda: ssm_lib.init_ssm_cache(cfg, batch, dtype),
                         cfg.num_layers),
            "attn": stack(lambda: attn_lib.init_kv_cache(cfg, batch, max_len, dtype),
                          n_groups),
        }
    if cfg.family == "ssm" and cfg.rwkv is not None:
        return {"rwkv": stack(lambda: rwkv_lib.init_rwkv_cache(cfg, batch, dtype),
                              cfg.num_layers)}
    if cfg.family == "ssm":
        return {"ssm": stack(lambda: ssm_lib.init_ssm_cache(cfg, batch, dtype),
                             cfg.num_layers)}
    return {"attn": stack(lambda: attn_lib.init_kv_cache(cfg, batch, max_len, dtype),
                          cfg.num_layers)}
