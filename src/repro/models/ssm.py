"""Mamba2 (SSD — state-space duality) blocks, chunked for MXU-friendly matmuls.

The selective state-space recurrence

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * (B_t ⊗ x_t)
    y_t = C_t · h_t + D * x_t

is evaluated with the chunked SSD algorithm: the sequence is split into
chunks of length Q; intra-chunk terms become (Q, Q)-masked matmuls (MXU
work), inter-chunk terms reduce to a short `lax.scan` over chunk states
(B, H, N, P).  Decode keeps the state plus a depthwise-conv tail buffer and
costs O(1) per token — this is what makes ``long_500k`` runnable.  A cache
holds the state as (B, H, P, N): the state size N (128 at published
widths) minor, so a TPU lays it out without padding and without
transposing it between a step's input and its update.

Layout follows Mamba2: in_proj -> [z | x | B | C | dt], depthwise causal
conv over the (x, B, C) channels, SSD core, gated RMSNorm, out_proj.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.common import ParamDef, dense, rmsnorm, shard
from repro.models.config import ModelConfig

__all__ = ["ssm_defs", "ssm_fwd", "init_ssm_cache", "ssd_chunked", "ssd_recurrent_ref"]


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    return s, d_inner, n_heads


def ssm_defs(cfg: ModelConfig) -> dict:
    """Separate projections per component (not one fused in_proj).

    A fused (D, 2*d_inner + 2GN + H) projection sharded over 'mlp' puts the
    split boundaries off the 16-way shard grid — XLA re-partitions each piece
    with thousands of masked select/slice ops inside the layer scan (measured
    ~45% of zamba2 train HBM traffic; §Perf pair 1, iteration 4).  Separate
    matrices shard each output on its natural axis; same math, same FLOPs.
    """
    s, d_inner, n_heads = _dims(cfg)
    gn = s.n_groups * s.state_dim
    return {
        "w_z": ParamDef((cfg.d_model, d_inner), ("embed", "mlp")),
        "w_x": ParamDef((cfg.d_model, d_inner), ("embed", "mlp")),
        "w_b": ParamDef((cfg.d_model, gn), ("embed", None)),
        "w_c": ParamDef((cfg.d_model, gn), ("embed", None)),
        "w_dt": ParamDef((cfg.d_model, n_heads), ("embed", "heads")),
        "conv_x_w": ParamDef((s.conv_kernel, d_inner), ("conv", "mlp")),
        "conv_x_b": ParamDef((d_inner,), ("mlp",), init="zeros"),
        "conv_bc_w": ParamDef((s.conv_kernel, 2 * gn), ("conv", None)),
        "conv_bc_b": ParamDef((2 * gn,), (None,), init="zeros"),
        "a_log": ParamDef((n_heads,), ("heads",), init="ssm_a"),
        "dt_bias": ParamDef((n_heads,), ("heads",), init="ssm_dt"),
        "d_skip": ParamDef((n_heads,), ("heads",), init="ones"),
        "norm": ParamDef((d_inner,), ("mlp",), init="ones"),
        "out_proj": ParamDef((d_inner, cfg.d_model), ("mlp", "embed")),
    }


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32) -> dict:
    s, d_inner, n_heads = _dims(cfg)
    gn = s.n_groups * s.state_dim
    return {
        "state": jnp.zeros((batch, n_heads, s.head_dim, s.state_dim), jnp.float32),
        "conv_x": jnp.zeros((batch, s.conv_kernel - 1, d_inner), dtype),
        "conv_bc": jnp.zeros((batch, s.conv_kernel - 1, 2 * gn), dtype),
    }


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_recurrent_ref(x, dt, a, b, c, init_state=None):
    """Step-by-step oracle.  x:(B,S,H,P) dt:(B,S,H) a:(H,) b,c:(B,S,G,N)."""
    bs, s, h, p = x.shape
    g = b.shape[2]
    rep = h // g
    state = (jnp.zeros((bs, h, b.shape[-1], p), jnp.float32)
             if init_state is None else init_state)

    def step(state, t):
        xt, dtt = x[:, t].astype(jnp.float32), dt[:, t]
        bt = jnp.repeat(b[:, t], rep, axis=1).astype(jnp.float32)   # (B,H,N)
        ct = jnp.repeat(c[:, t], rep, axis=1).astype(jnp.float32)
        da = jnp.exp(dtt * a)                                       # (B,H)
        state = (state * da[..., None, None]
                 + jnp.einsum("bhn,bhp->bhnp", bt, xt * dtt[..., None]))
        y = jnp.einsum("bhn,bhnp->bhp", ct, state)
        return state, y

    state, ys = lax.scan(step, state, jnp.arange(s))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), state


def ssd_chunked(x, dt, a, b, c, chunk: int, init_state=None):
    """Chunked SSD.  Same signature/semantics as the oracle, O(S·Q) matmuls."""
    bs, s, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = x.shape[1]
    nc = sp // chunk

    f32 = jnp.float32
    xc = x.reshape(bs, nc, chunk, h, p).astype(f32)
    dtc = dt.reshape(bs, nc, chunk, h).astype(f32)
    bc = jnp.repeat(b.reshape(bs, nc, chunk, g, n), rep, axis=3).astype(f32)
    cc = jnp.repeat(c.reshape(bs, nc, chunk, g, n), rep, axis=3).astype(f32)

    la = dtc * a                                   # (B,C,Q,H) log-decay per step
    # inclusive cumsum as a triangular matmul: jnp.cumsum lowers to an
    # associative-scan tree of thousands of small slice/select ops inside the
    # layer scan (measured ~19% of zamba2 train HBM traffic); one (Q,Q) dot on
    # the MXU replaces it (§Perf pair 1, iteration 3).
    tril = jnp.tril(jnp.ones((chunk, chunk), f32))
    cum = jnp.einsum("qt,bcth->bcqh", tril, la)    # inclusive cumsum
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # (B,C,Qi,Qj,H)
    idx = jnp.arange(chunk)
    causal = (idx[:, None] >= idx[None, :])[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(seg), 0.0)

    xdt = xc * dtc[..., None]                      # dt-weighted input
    # intra-chunk: y[i] = sum_{j<=i} (C_i.B_j) * exp(cum_i - cum_j) * xdt_j
    cb = jnp.einsum("bcihn,bcjhn->bcijh", cc, bc)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", cb * decay, xdt)

    # chunk summary state: sum_j exp(cum_last - cum_j) * B_j ⊗ xdt_j
    tail = jnp.exp(cum[:, :, -1:, :] - cum)        # (B,C,Q,H)
    chunk_state = jnp.einsum("bcjhn,bcjhp->bchnp", bc * tail[..., None], xdt)
    chunk_decay = jnp.exp(jnp.sum(la, axis=2))     # (B,C,H)

    # inter-chunk scan over chunk states
    state0 = (jnp.zeros((bs, h, n, p), f32) if init_state is None
              else init_state.astype(f32))

    def chunk_step(state, inp):
        cs, cd = inp                               # (B,H,N,P), (B,H)
        prev = state
        state = state * cd[..., None, None] + cs
        return state, prev

    final_state, prev_states = lax.scan(
        chunk_step, state0,
        (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B,C,H,N,P)

    # inter-chunk contribution: C_i · (exp(cum_i) * state_entering_chunk)
    y_inter = jnp.einsum("bcihn,bchnp->bcihp",
                         cc * jnp.exp(cum)[..., None], prev_states)

    y = (y_intra + y_inter).reshape(bs, sp, h, p)[:, :s]
    return y.astype(x.dtype), final_state


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------

def _causal_conv(seq, conv_w, conv_b, tail=None, lengths=None):
    """Depthwise causal conv along seq.  seq: (B,S,C); tail: (B,K-1,C).

    The new tail is each row's last K-1 inputs: those before ``lengths``
    (B,) when given, else the sequence's last.
    """
    k = conv_w.shape[0]
    if tail is None:
        tail = jnp.zeros((seq.shape[0], k - 1, seq.shape[2]), seq.dtype)
    full = jnp.concatenate([tail.astype(seq.dtype), seq], axis=1)
    out = jnp.zeros_like(seq)
    for i in range(k):
        out = out + full[:, i:i + seq.shape[1]] * conv_w[i].astype(seq.dtype)
    out = out + conv_b.astype(seq.dtype)
    if lengths is None:
        new_tail = full[:, full.shape[1] - (k - 1):]
    else:  # input t sits at full[t + k - 1]
        new_tail = jax.vmap(lambda f, n: lax.dynamic_slice_in_dim(
            f, n, k - 1, axis=0))(full, lengths)
    return jax.nn.silu(out), new_tail


def state_scope(step: bool):
    """The named scope of a Mamba2 layer's recurrence and of its state's
    read and write-back: ``ssm_step`` for a one-token step with a cache,
    ``ssd_scan`` for the chunked scan."""
    return jax.named_scope("ssm_step" if step else "ssd_scan")


def ssm_fwd(params: dict, x: jax.Array, cfg: ModelConfig, *,
            cache: dict | None = None, lengths=None):
    """x: (B, S, D) -> (out, new_cache_or_None).

    ``lengths`` (B,), for a right-padded prompt: each row's true length.
    ``dt`` is 0 past it, so the state stops changing there (decay 1, no
    input), and the conv tails are the row's last real inputs.

    The recurrence between the projections (conv and tail shift, ``dt``,
    the SSD scan or single step, the ``D`` skip) runs under
    ``state_scope``.
    """
    s, d_inner, n_heads = _dims(cfg)
    gn = s.n_groups * s.state_dim
    z = shard(dense(params["w_z"], x, cfg, name="w_z"), "batch", None, "mlp")
    xin = shard(dense(params["w_x"], x, cfg, name="w_x"), "batch", None, "mlp")
    bc = jnp.concatenate(
        [dense(params["w_b"], x, cfg, name="w_b"), dense(params["w_c"], x, cfg, name="w_c")], axis=-1)
    dt = shard(dense(params["w_dt"], x, cfg, name="w_dt"), "batch", None, "heads")

    bsz, slen = x.shape[0], x.shape[1]
    step = slen == 1 and cache is not None
    with state_scope(step):
        tail_x = cache["conv_x"] if cache is not None else None
        tail_bc = cache["conv_bc"] if cache is not None else None
        xin, new_tail_x = _causal_conv(xin, params["conv_x_w"],
                                       params["conv_x_b"], tail_x, lengths)
        bc, new_tail_bc = _causal_conv(bc, params["conv_bc_w"],
                                       params["conv_bc_b"], tail_bc, lengths)
        xin = shard(xin, "batch", None, "mlp")
        bb, cc = jnp.split(bc, [gn], axis=-1)

        xh = xin.reshape(bsz, slen, n_heads, s.head_dim)
        bh = bb.reshape(bsz, slen, s.n_groups, s.state_dim)
        ch = cc.reshape(bsz, slen, s.n_groups, s.state_dim)
        a = -jnp.exp(params["a_log"].astype(jnp.float32))
        dt_full = jax.nn.softplus(dt.astype(jnp.float32)
                                  + params["dt_bias"].astype(jnp.float32))
        if lengths is not None:
            live = jnp.arange(slen)[None, :] < lengths[:, None]
            dt_full = jnp.where(live[..., None], dt_full, 0.0)

        init_state = cache["state"] if cache is not None else None
        if step:
            # O(1) decode step.
            rep = n_heads // s.n_groups
            xt, dtt = xh[:, 0].astype(jnp.float32), dt_full[:, 0]
            bt = jnp.repeat(bh[:, 0], rep, axis=1).astype(jnp.float32)
            ct = jnp.repeat(ch[:, 0], rep, axis=1).astype(jnp.float32)
            da = jnp.exp(dtt * a)
            state = (init_state * da[..., None, None]
                     + jnp.einsum("bhp,bhn->bhpn", xt * dtt[..., None], bt))
            yh = jnp.einsum("bhpn,bhn->bhp", state, ct)[:, None]
            final_state = state
        else:
            yh, final_state = ssd_chunked(
                xh, dt_full, a, bh, ch, s.chunk,
                init_state=None if init_state is None
                else jnp.swapaxes(init_state, -1, -2))
            final_state = jnp.swapaxes(final_state, -1, -2)
        yh = yh + params["d_skip"].astype(yh.dtype)[None, None, :, None] * xh.astype(yh.dtype)
    y = yh.reshape(bsz, slen, d_inner).astype(x.dtype)

    y = rmsnorm(params["norm"], y * jax.nn.silu(z), cfg.rms_eps)
    out = dense(params["out_proj"], y, cfg, name="out_proj")
    out = shard(out, "batch", None, None)
    new_cache = None
    if cache is not None:
        new_cache = {"state": final_state,
                     "conv_x": new_tail_x.astype(cache["conv_x"].dtype),
                     "conv_bc": new_tail_bc.astype(cache["conv_bc"].dtype)}
    return out, new_cache
