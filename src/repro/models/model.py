"""Top-level language model: embeddings -> layer stack -> norm -> logits.

Provides the three entry points the launch layer jits:
  * ``forward``       — logits for a full sequence (train / prefill)
  * ``prefill``       — forward + populated KV/state caches
  * ``decode_step``   — one token with caches (serve_step)
plus parameter/cache initialization and their `PartitionSpec` trees.

Modality frontends ([audio]/[vlm]) are stubs per the assignment: when
``cfg.frontend_stub``, ``forward`` accepts precomputed frame/patch embeddings
(B, S, D) instead of token ids (the backbone is the deliverable).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import blocks as blocks_lib
from repro.models.common import (ParamDef, dense, dtype_of, embed_lookup,
                                 init_tree, logits_from_embedding, pspec_tree,
                                 rmsnorm, rules_for, shard)
from repro.models.config import ModelConfig

__all__ = [
    "model_defs", "init_params", "param_pspecs", "cache_pspecs",
    "forward", "prefill", "decode_step", "init_caches", "loss_fn",
    "count_params", "embed_in", "logits_out",
]


def model_defs(cfg: ModelConfig) -> dict:
    defs: dict = {
        "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          init="normal"),
        "final_norm": ParamDef((cfg.d_model,), ("embed",), init="ones"),
        "layers": blocks_lib.stacked_layer_defs(cfg),
    }
    if cfg.family == "hybrid" and cfg.layer_types is None:
        defs["shared"] = blocks_lib.shared_attn_defs(cfg)
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"))
    return defs


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    return init_tree(model_defs(cfg), key, dtype_of(cfg.param_dtype))


def param_pspecs(cfg: ModelConfig, mesh, phase: str = "train") -> dict:
    rules = rules_for(cfg)
    if phase == "inference" and cfg.fsdp and not cfg.fsdp_inference:
        # serving layout: no FSDP — weights replicate over 'data', killing
        # the per-step weight all-gathers (§Perf pair 3 residual finding)
        rules["embed"] = None
    return pspec_tree(model_defs(cfg), rules, tuple(mesh.axis_names),
                      mesh_shape=dict(mesh.shape))


def cache_pspecs(cfg: ModelConfig, mesh, batch: int = 0, max_len: int = 0):
    """PartitionSpec tree matching init_caches (stacked leading layer axis).

    Pass the real (batch, max_len) so non-divisible dims (batch=1 long_500k)
    fall back to replication consistently with the lowered shapes.
    """
    rules = rules_for(cfg)
    axes = tuple(mesh.axis_names)
    mesh_shape = dict(mesh.shape)
    caches = jax.eval_shape(
        lambda: init_caches(cfg, batch=batch or 8, max_len=max_len or 64))

    def spec_for(path, leaf):
        names = [str(getattr(p, "key", "")) for p in path]
        if "attn" in names:
            if leaf.ndim == 4:   # (L, B, S, rank/rd) MLA latent
                logical = (None, "batch", "kv_seq", None)
            else:                 # (L, B, S, KVH, hd)
                logical = (None, "batch", "kv_seq", None, None)
        else:                     # ssm/rwkv states & conv tails: batch only
            logical = (None, "batch") + (None,) * (leaf.ndim - 2)
        from repro.models.common import logical_to_pspec
        return logical_to_pspec(logical, rules, axes, shape=tuple(leaf.shape),
                                mesh_shape=mesh_shape)

    flat = jax.tree_util.tree_flatten_with_path(caches)
    specs = [spec_for(path, leaf) for path, leaf in flat[0]]
    return jax.tree_util.tree_unflatten(flat[1], specs)


def _embed_in(params, cfg: ModelConfig, tokens=None, embeds=None):
    compute = dtype_of(cfg.compute_dtype)
    if embeds is not None:
        x = embeds.astype(compute)
    else:
        x = embed_lookup(params["embed"], tokens, compute)
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(compute)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, compute)
    return shard(x, "batch", None, None)


def _logits_out(params, cfg: ModelConfig, x):
    x = rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if cfg.tie_embeddings:
        # tied head: the transposed-embedding matmul stays float (the
        # backend/plan scopes cover weight-stationary GEMM sites)
        logits = logits_from_embedding(params["embed"], x, cfg.logit_softcap)
    else:
        from repro.backends import runtime as backend_runtime
        if backend_runtime.active_execution() is not None:
            # plannable "lm_head" site under a backend/plan scope; outside
            # any scope the head keeps its historical plain-float matmul
            # (in particular it never enters the cfg.quant_kernel path)
            logits = dense(params["lm_head"], x, cfg, name="lm_head")
        else:
            with jax.named_scope("lm_head"):
                logits = jnp.matmul(x, params["lm_head"].astype(x.dtype))
        if cfg.logit_softcap is not None:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return shard(logits, "batch", None, "vocab")


# Public aliases: the serving engine (repro.serving.engine) drives its own
# ragged paged decode loop over the layer stack but must share the
# embedding/head math with decode_step *exactly* — its paged-vs-contiguous
# bit-exactness tests compare full logits between the two paths.
embed_in = _embed_in
logits_out = _logits_out


def forward(params: dict, cfg: ModelConfig, tokens=None, *, embeds=None,
            positions=None):
    """Full-sequence logits.  Returns (logits (B,S,V), aux_loss)."""
    x = _embed_in(params, cfg, tokens, embeds)
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    x, _, aux = blocks_lib.stack_fwd(params, x, cfg, positions=positions)
    return _logits_out(params, cfg, x), aux


def init_caches(cfg: ModelConfig, batch: int, max_len: int,
                dtype=jnp.bfloat16) -> dict:
    return blocks_lib.init_layer_caches(cfg, batch, max_len, dtype)


def prefill(params: dict, cfg: ModelConfig, tokens=None, *, caches,
            embeds=None, lengths=None):
    """Populate caches from a prompt.  Returns (logits, new_caches).

    ``lengths`` (B,): each row's true length in a right-padded batch.  A
    layer pattern's SSM states and conv tails are then taken there (an
    attention cache needs no such care: causal rows ignore the padding),
    and the logits are each row's at its last position only, (B, 1, V).
    """
    x = _embed_in(params, cfg, tokens, embeds)
    s = x.shape[1]
    positions = jnp.arange(s)[None, :]
    x, new_caches, _ = blocks_lib.stack_fwd(
        params, x, cfg, positions=positions, caches=caches, cache_pos=0,
        kv_valid_len=jnp.full((x.shape[0],), s, jnp.int32), lengths=lengths)
    if lengths is not None:
        x = jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
    return _logits_out(params, cfg, x), new_caches


def decode_step(params: dict, cfg: ModelConfig, tokens, *, caches, cache_pos):
    """One decode step.  tokens: (B, 1); cache_pos: scalar int (shared).

    Returns (logits (B, 1, V), new_caches).
    """
    x = _embed_in(params, cfg, tokens)
    positions = jnp.full((x.shape[0], 1), cache_pos, jnp.int32)
    x, new_caches, _ = blocks_lib.stack_fwd(
        params, x, cfg, positions=positions, caches=caches,
        cache_pos=cache_pos, kv_valid_len=cache_pos + 1)
    return _logits_out(params, cfg, x), new_caches


def loss_fn(params: dict, cfg: ModelConfig, tokens, targets, *,
            aux_weight: float = 0.01, embeds=None):
    """Mean next-token cross-entropy (+ MoE aux).  targets: (B, S) int32."""
    logits, aux = forward(params, cfg, tokens, embeds=embeds)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll + aux_weight * aux, {"nll": nll, "aux": aux}


def count_params(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
