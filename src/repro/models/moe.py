"""Mixture-of-Experts with expert parallelism over the ``model`` mesh axis.

Baseline EP ("psum"): expert weights are sharded over ``model`` inside a
``shard_map``; every rank routes the *same* (data-sharded, model-replicated)
tokens, computes only its local experts' contributions via capacity-bounded
gather -> FFN -> weighted scatter-add, and a single ``psum`` over ``model``
combines.  One (T_local, D) all-reduce per MoE layer — simple and robust.

Optimized EP ("a2a"): tokens are exchanged with ``all_to_all`` so each rank
runs its experts on a (E_local * C, D) buffer instead of scoring all tokens,
replacing the big combine all-reduce with two smaller all-to-alls.  This is a
§Perf hillclimb lever; both paths produce identical outputs when capacity is
not exceeded.

Routing: softmax (Switch/Mixtral) or sigmoid (DeepSeek-V3) scoring, top-k with
renormalization, optional shared (always-on) experts, and a Switch-style
load-balance auxiliary loss.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.common import ParamDef, shard
from repro.models.config import ModelConfig
from repro.backends.runtime import site_scope
from repro.models.mlp import mlp_defs, mlp_fwd

__all__ = ["moe_defs", "moe_fwd"]


def moe_defs(cfg: ModelConfig) -> dict:
    assert cfg.moe is not None
    m, d = cfg.moe, cfg.d_model
    ffe = m.d_ff_expert
    defs = {
        "router": ParamDef((d, m.num_experts), ("embed", "experts")),
        "w_gate": ParamDef((m.num_experts, d, ffe), ("experts", "embed", "expert_mlp"),
                           fan_in_axes=(1,)),
        "w_up": ParamDef((m.num_experts, d, ffe), ("experts", "embed", "expert_mlp"),
                         fan_in_axes=(1,)),
        "w_down": ParamDef((m.num_experts, ffe, d), ("experts", "expert_mlp", "embed"),
                           fan_in_axes=(1,)),
    }
    if m.num_shared_experts:
        defs["shared"] = mlp_defs(cfg, d_ff=m.num_shared_experts * ffe)
    return defs


def _routing(router_w, x_flat, cfg: ModelConfig, scoring: str = "softmax"):
    """-> (topk_idx (T,K), topk_w (T,K), probs (T,E))."""
    m = cfg.moe
    logits = jnp.matmul(x_flat.astype(jnp.float32),
                        router_w.astype(jnp.float32))          # (T, E)
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = lax.top_k(probs, m.top_k)
    topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)
    return topk_idx, topk_w, probs


def _capacity(t_local: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = math.ceil(t_local * m.top_k / m.num_experts * m.capacity_factor)
    return min(t_local, max(4, c))


def _local_expert_pass(x_flat, topk_idx, topk_w, wg, wu, wd, cfg: ModelConfig,
                       first_global_expert):
    """Capacity-gather each local expert's tokens, FFN, weighted scatter-add.

    x_flat: (T, D);  wg/wu/wd: (E_local, ...) local expert stacks.
    Returns the summed contribution (T, D) of the local experts.
    """
    t_local, d = x_flat.shape
    e_local = wg.shape[0]
    cap = _capacity(t_local, cfg)

    def one_expert(acc, inputs):
        w_g, w_u, w_d, local_e = inputs
        global_e = first_global_expert + local_e
        # per-token weight for this expert (0 if not routed here)
        hit = (topk_idx == global_e)                         # (T, K)
        w_tok = jnp.sum(jnp.where(hit, topk_w, 0.0), axis=-1)  # (T,)
        sel_w, sel_idx = lax.top_k(w_tok, cap)               # capacity selection
        xs = jnp.take(x_flat, sel_idx, axis=0)               # (C, D)
        h = jax.nn.silu(jnp.matmul(xs, w_g.astype(xs.dtype))) * jnp.matmul(
            xs, w_u.astype(xs.dtype))
        y = jnp.matmul(h, w_d.astype(xs.dtype))              # (C, D)
        y = y * sel_w[:, None].astype(y.dtype)               # weight (0 for non-routed)
        acc = acc.at[sel_idx].add(y)
        return acc, None

    acc0 = jnp.zeros_like(x_flat)
    acc, _ = lax.scan(one_expert, acc0,
                      (wg, wu, wd, jnp.arange(e_local)))
    return acc


def _aux_loss(probs, topk_idx, cfg: ModelConfig):
    """Switch-style load-balance loss: E * sum_e f_e * p_e."""
    m = cfg.moe
    e = m.num_experts
    hits = jax.nn.one_hot(topk_idx[..., 0], e, dtype=jnp.float32)  # primary expert
    f = jnp.mean(hits, axis=0)
    p = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * p)


def _current_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def moe_fwd(params: dict, x: jax.Array, cfg: ModelConfig,
            scoring: str = "softmax"):
    """x: (B, S, D) -> (out (B, S, D), aux_loss scalar)."""
    m = cfg.moe
    b, s, d = x.shape
    x_flat = x.reshape(-1, d)
    mesh = _current_mesh()
    use_ep = (mesh is not None and "model" in mesh.axis_names
              and mesh.shape["model"] > 1 and m.num_experts % mesh.shape["model"] == 0)

    if use_ep:
        n_model = mesh.shape["model"]
        a2a_ok = (m.ep_impl == "a2a" and x_flat.shape[0] % n_model == 0
                  and x_flat.shape[0] >= n_model * n_model)
        if a2a_ok:
            out_flat, aux = _moe_ep_a2a(params, x_flat, cfg, mesh, scoring)
        else:
            out_flat, aux = _moe_ep_psum(params, x_flat, cfg, mesh, scoring)
    else:
        topk_idx, topk_w, probs = _routing(params["router"], x_flat, cfg, scoring)
        out_flat = _local_expert_pass(x_flat, topk_idx, topk_w, params["w_gate"],
                                      params["w_up"], params["w_down"], cfg, 0)
        aux = _aux_loss(probs, topk_idx, cfg)

    out = out_flat.reshape(b, s, d)
    if m.num_shared_experts:
        # site path matches the param tree ("…/moe/shared/w_up"); the routed
        # experts' batched einsums are not dense sites and stay float under
        # backend/plan scopes (see docs/PLANNER.md coverage notes)
        with site_scope("shared"):
            out = out + mlp_fwd(params["shared"], x, cfg)
    return shard(out, "batch", None, None), aux


def _batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _moe_ep_psum(params, x_flat, cfg: ModelConfig, mesh, scoring):
    m = cfg.moe
    baxes = _batch_axes(mesh)

    def block(router_w, wg, wu, wd, xb):
        rank = lax.axis_index("model")
        e_local = wg.shape[0]
        topk_idx, topk_w, probs = _routing(router_w, xb, cfg, scoring)
        contrib = _local_expert_pass(xb, topk_idx, topk_w, wg, wu, wd, cfg,
                                     rank * e_local)
        out = lax.psum(contrib, "model")
        aux = _aux_loss(probs, topk_idx, cfg)   # identical on every rank
        return out, aux

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P(baxes)),
        out_specs=(P(baxes), P()),
        check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x_flat)


def _moe_ep_a2a(params, x_flat, cfg: ModelConfig, mesh, scoring):
    """All-to-all dispatch EP (§Perf optimized variant).

    Per rank: route local tokens, build (E, C_out) send buffers, all_to_all to
    expert owners, run local experts on (ranks * E_local * C_out) rows,
    all_to_all back, weighted scatter-add.  Collective volume:
    2 * E * C_out * D per rank vs. psum's T_local * D all-reduce.
    """
    m = cfg.moe
    baxes = _batch_axes(mesh)
    n_model = mesh.shape["model"]

    def block(router_w, wg, wu, wd, xb):
        rank = lax.axis_index("model")
        t_local, d = xb.shape
        e = m.num_experts
        e_local = e // n_model
        # Each model-rank handles a distinct slice of the data-parallel tokens
        # (tokens arrive replicated over 'model'; slice so ranks don't repeat
        # work, at the price of an extra gather at the end).
        t_slice = t_local // n_model
        xb_my = lax.dynamic_slice_in_dim(xb, rank * t_slice, t_slice, 0)
        topk_idx, topk_w, probs = _routing(router_w, xb_my, cfg, scoring)
        cap = _capacity(t_slice, cfg)

        # Build per-expert send buffers (E, C, D) + weights + source rows.
        w_tok = jnp.zeros((t_slice, e), xb.dtype)
        w_tok = jax.vmap(lambda wt, ti, tw: wt.at[ti].add(tw))(
            w_tok, topk_idx, topk_w.astype(xb.dtype))        # (T_s, E)
        sel_w, sel_idx = lax.top_k(w_tok.T, cap)              # (E, C)
        send = jnp.take(xb_my, sel_idx.reshape(-1), axis=0).reshape(e, cap, d)
        # (E, C, D) -> regroup as (n_model, E_local, C, D) and exchange.
        send = send.reshape(n_model, e_local, cap, d)
        recv = lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                              tiled=False)                    # (n_model, E_local, C, D)
        recv = jnp.moveaxis(recv, 1, 0)                       # (E_local, n_model, C, D)
        recv = recv.reshape(e_local, n_model * cap, d)

        def run_expert(args):
            w_g, w_u, w_d, xs = args
            h = jax.nn.silu(jnp.matmul(xs, w_g.astype(xs.dtype))) * jnp.matmul(
                xs, w_u.astype(xs.dtype))
            return jnp.matmul(h, w_d.astype(xs.dtype))

        ys = jax.vmap(lambda w_g, w_u, w_d, xs: run_expert((w_g, w_u, w_d, xs)))(
            wg, wu, wd, recv)                                 # (E_local, n_model*C, D)
        ys = ys.reshape(e_local, n_model, cap, d)
        ys = jnp.moveaxis(ys, 1, 0)                           # (n_model, E_local, C, D)
        back = lax.all_to_all(ys, "model", split_axis=0, concat_axis=0,
                              tiled=False)                    # (n_model, E_local, C, D)
        back = back.reshape(e, cap, d)

        out_my = jnp.zeros((t_slice, d), xb.dtype)
        out_my = out_my.at[sel_idx.reshape(-1)].add(
            (back * sel_w[..., None].astype(back.dtype)).reshape(-1, d))
        # Reassemble the full local token block across model ranks.
        out = jnp.zeros((t_local, d), xb.dtype)
        out = lax.dynamic_update_slice_in_dim(out, out_my, rank * t_slice, 0)
        out = lax.psum(out, "model")
        aux = lax.psum(_aux_loss(probs, topk_idx, cfg), "model") / n_model
        return out, aux

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(), P("model"), P("model"), P("model"), P(baxes)),
        out_specs=(P(baxes), P()),
        check_vma=False)
    return fn(params["router"], params["w_gate"], params["w_up"],
              params["w_down"], x_flat)
