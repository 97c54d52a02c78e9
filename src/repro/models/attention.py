"""Attention: GQA/MQA/MHA and MLA (DeepSeek), train/prefill + cached decode.

Long sequences use blockwise (flash-style, online-softmax) attention — a
double `lax.scan` over query/KV chunks — so 32k-token prefill never
materializes the full (S, S) score matrix.  Decode attends against a KV cache
whose sequence axis is sharded over the ``model`` mesh axis (flash-decoding:
XLA inserts the distributed max/sum for the partial softmax).

MLA keeps the compressed latent (c_kv, k_rope) as the cache — the ~9x cache
shrink vs. GQA is visible in the dry-run bytes — and decodes in the absorbed
form (W_uk folded into the query) so no per-head K/V are ever materialized at
decode time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import rope as rope_lib
from repro.models.common import ParamDef, dense, rmsnorm, shard
from repro.models.config import ModelConfig

__all__ = [
    "gqa_defs", "mla_defs", "attention_defs",
    "init_kv_cache", "attention_fwd",
    "naive_attention", "blockwise_attention",
    "BLOCKWISE_THRESHOLD",
]

BLOCKWISE_THRESHOLD = 8192   # switch to chunked attention above this seq len
# (a 2048 threshold was tried during the zamba2 memory iteration and REFUTED:
#  XLA chunked attention still round-trips score tiles through HBM and adds
#  correction passes — measured WORSE at 4k for zamba2/chameleon/deepseek.
#  Blockwise is kept for >=8k where O(S^2) peak memory forces it; on TPU the
#  fused Pallas flash kernel takes over at every length.)
Q_CHUNK = 2048
KV_CHUNK = 2048


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

def gqa_defs(cfg: ModelConfig) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamDef((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamDef((d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamDef((d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed"),
                       fan_in_axes=(0, 1)),
    }


def mla_defs(cfg: ModelConfig) -> dict:
    assert cfg.mla is not None
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.nope_head_dim + m.rope_head_dim
    return {
        "w_dq": ParamDef((d, m.q_lora_rank), ("embed", "lora")),
        "q_norm": ParamDef((m.q_lora_rank,), ("lora",), init="ones"),
        "w_uq": ParamDef((m.q_lora_rank, h, qk), ("lora", "heads", "head_dim")),
        "w_dkv": ParamDef((d, m.kv_lora_rank), ("embed", "lora")),
        "kv_norm": ParamDef((m.kv_lora_rank,), ("lora",), init="ones"),
        "w_kr": ParamDef((d, m.rope_head_dim), ("embed", "head_dim")),
        "w_uk": ParamDef((m.kv_lora_rank, h, m.nope_head_dim),
                         ("lora", "heads", "head_dim")),
        "w_uv": ParamDef((m.kv_lora_rank, h, m.v_head_dim),
                         ("lora", "heads", "head_dim")),
        "wo": ParamDef((h, m.v_head_dim, d), ("heads", "head_dim", "embed"),
                       fan_in_axes=(0, 1)),
    }


def attention_defs(cfg: ModelConfig) -> dict:
    return mla_defs(cfg) if cfg.attention == "mla" else gqa_defs(cfg)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Zeroed cache pytree for one attention layer-instance."""
    if cfg.attention == "mla":
        m = cfg.mla
        return {
            "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
        }
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, kvh, hd), dtype),
        "v": jnp.zeros((batch, max_len, kvh, hd), dtype),
    }


def kv_cache_pspec(cfg: ModelConfig, rules, mesh_axes):
    """Logical shardings for the cache (seq axis over 'model')."""
    from repro.models.common import logical_to_pspec as l2p
    if cfg.attention == "mla":
        return {
            "ckv": l2p(("batch", "kv_seq", None), rules, mesh_axes),
            "krope": l2p(("batch", "kv_seq", None), rules, mesh_axes),
        }
    spec = l2p(("batch", "kv_seq", None, None), rules, mesh_axes)
    return {"k": spec, "v": spec}


# ---------------------------------------------------------------------------
# Score computation
# ---------------------------------------------------------------------------

def naive_attention(q, k, v, *, causal: bool, q_offset=0,
                    kv_valid_len=None, scale: float | None = None) -> jax.Array:
    """q: (B,Sq,H,D), k/v: (B,Skv,H,D) -> (B,Sq,H,Dv).  f32 softmax.

    ``scale``: the score scale, by default ``1/sqrt(D)``."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if scale is None:
        scores = scores / jnp.sqrt(jnp.float32(d))
    else:
        scores = scores * jnp.float32(scale)
    sq, sk = q.shape[1], k.shape[1]
    mask = None
    if causal:
        qpos = jnp.arange(sq)[:, None] + q_offset
        kpos = jnp.arange(sk)[None, :]
        mask = qpos >= kpos
    if kv_valid_len is not None:
        valid = jnp.arange(sk)[None, :] < jnp.asarray(kv_valid_len).reshape(-1, 1)
        valid = valid[:, None, None, :]  # (B,1,1,Sk)
        mask = valid if mask is None else (mask[None, None] & valid)
    elif mask is not None:
        mask = mask[None, None]
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def blockwise_attention(q, k, v, *, causal: bool,
                        q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK) -> jax.Array:
    """Flash-style online-softmax attention; never materializes (Sq, Skv)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    dv = v.shape[-1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    if sq % q_chunk or skv % kv_chunk:
        raise ValueError(f"seq lens ({sq},{skv}) must divide chunks ({q_chunk},{kv_chunk})")
    nq, nk = sq // q_chunk, skv // kv_chunk
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    qc = q.reshape(b, nq, q_chunk, h, d)
    kc = k.reshape(b, nk, kv_chunk, h, d)
    vc = v.reshape(b, nk, kv_chunk, h, dv)

    def q_step(_, qi):
        qblk = qc[:, qi]  # (B, qc, H, D)

        def kv_step(carry, ki):
            m_prev, l_prev, acc = carry
            kblk, vblk = kc[:, ki], vc[:, ki]
            s = jnp.einsum("bqhd,bkhd->bhqk", qblk, kblk).astype(jnp.float32) * scale
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None]
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
                s = jnp.where((qpos >= kpos)[None, None], s, -1e30)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vblk.astype(jnp.float32))
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, h, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, dv), jnp.float32)
        # causal: KV chunks beyond the diagonal contribute nothing; still
        # scanned for static shape, masked to -inf (cheap relative to matmul).
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.transpose(0, 2, 1, 3)  # (B, qc, H, Dv)

    _, outs = lax.scan(q_step, None, jnp.arange(nq))  # (nq, B, qc, H, Dv)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv).astype(v.dtype)


def _mixed_attention(q, k, v, *, causal: bool) -> jax.Array:
    """Backend-dispatching attention for full-sequence (no-cache) paths.

    TPU: the fused Pallas flash kernel (kernels/flash_attention.py) — score
    tiles stay in VMEM, HBM traffic is Q/K/V/O only.  CPU (this container):
    blockwise above BLOCKWISE_THRESHOLD, naive below (XLA cannot fuse the
    softmax(QKᵀ)V chain, so score chunks round-trip HBM either way — see
    EXPERIMENTS.md §Perf pair 1 for the measured delta the kernel removes).
    """
    if jax.default_backend() == "tpu":  # pragma: no cover - TPU path
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal)
    if q.shape[1] > BLOCKWISE_THRESHOLD:
        return blockwise_attention(q, k, v, causal=causal)
    return naive_attention(q, k, v, causal=causal)


def _repeat_kv(kv: jax.Array, h: int) -> jax.Array:
    kvh = kv.shape[2]
    if kvh == h:
        return kv
    return jnp.repeat(kv, h // kvh, axis=2)


def _current_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _sharded_decode_attention(q, kc, vc, h: int, *, q_offset, kv_valid_len,
                              mesh) -> jax.Array:
    """Explicit flash-decoding over the seq-sharded KV cache (shard_map).

    XLA's SPMD partitioner will NOT distribute a softmax whose reduction axis
    is sharded — it all-gathers K/V instead (measured 2 x 34 GB per decode
    step for llama3 decode_32k).  This shard_map computes shard-local partial
    (max, sumexp, context) and combines with the log-sum-exp trick: the only
    collectives are a pmax/psum of (B, H, 1)-sized stats and the (B, H, 1, d)
    partial context — a few MB.

    q: (B, Sq, H, hd) replicated over 'model'; kc/vc: (B, Smax, KVH, hd)
    seq-sharded over 'model'.
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.common import shardable_batch_axes
    baxes = shardable_batch_axes(mesh, q.shape[0], candidates=("pod", "data"))
    n_model = mesh.shape["model"]
    s_local = kc.shape[1] // n_model

    def block(qb, kb, vb, q_off, valid):
        rank = lax.axis_index("model")
        kb = _repeat_kv(kb.astype(qb.dtype), h)
        vb = _repeat_kv(vb.astype(qb.dtype), h)
        d = qb.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb).astype(jnp.float32)
        s = s / jnp.sqrt(jnp.float32(d))
        sq = qb.shape[1]
        qpos = jnp.arange(sq)[:, None] + q_off
        kpos = rank * s_local + jnp.arange(s_local)[None, :]
        mask = (qpos >= kpos) & (kpos < valid)
        s = jnp.where(mask[None, None], s, -1e30)
        m = jnp.max(s, axis=-1)                              # (B,H,Sq)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb.dtype), vb)
        m_g = lax.pmax(m, "model")
        alpha = jnp.exp(m - m_g)
        l_g = lax.psum(l * alpha, "model")
        ctx_g = lax.psum(ctx * alpha[..., None].astype(ctx.dtype), "model")
        out = ctx_g / jnp.maximum(l_g[..., None], 1e-30).astype(ctx_g.dtype)
        return out.transpose(0, 2, 1, 3)                     # (B,Sq,H,hd)

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(baxes), P(baxes, "model"), P(baxes, "model"), P(), P()),
        out_specs=P(baxes),
        check_vma=False)
    return fn(q, kc, vc, jnp.asarray(q_offset, jnp.int32),
              jnp.asarray(kv_valid_len, jnp.int32).reshape(()))


def _update_cache(cache_arr: jax.Array, new: jax.Array, pos) -> jax.Array:
    """Write ``new`` (B, S_new, ...) into the seq axis at ``pos`` (scalar)."""
    return lax.dynamic_update_slice_in_dim(cache_arr, new.astype(cache_arr.dtype),
                                           pos, axis=1)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def attention_fwd(params: dict, x: jax.Array, cfg: ModelConfig, *,
                  positions: jax.Array, cache: dict | None = None,
                  cache_pos=0, kv_valid_len=None):
    """Returns (out (B,S,D), new_cache_or_None)."""
    if cfg.attention == "mla":
        return _mla_fwd(params, x, cfg, positions=positions, cache=cache,
                        cache_pos=cache_pos, kv_valid_len=kv_valid_len)
    return _gqa_fwd(params, x, cfg, positions=positions, cache=cache,
                    cache_pos=cache_pos, kv_valid_len=kv_valid_len)


def _gqa_fwd(params, x, cfg, *, positions, cache, cache_pos, kv_valid_len):
    h, hd = cfg.num_heads, cfg.resolved_head_dim
    q = dense(params["wq"], x, cfg, name="wq")         # (B,S,H,hd)
    k = dense(params["wk"], x, cfg, name="wk")         # (B,S,KVH,hd)
    v = dense(params["wv"], x, cfg, name="wv")
    if cfg.position_embedding == "rope":
        q = rope_lib.apply_rope(q, positions, cfg.rope_theta)
        k = rope_lib.apply_rope(k, positions, cfg.rope_theta)
    if cfg.attn_scale is not None:
        # every attention below divides the scores by sqrt(hd): fold the
        # configured scale into q (exact where the ratio is a power of two,
        # as granite-4.0-h's 1/64 against 1/8 is)
        q = q * jnp.asarray(cfg.attn_scale * hd ** 0.5, q.dtype)
    q = shard(q, "batch", None, "heads", "head_dim")

    new_cache = None
    if cache is not None:
        kc = _update_cache(cache["k"], k, cache_pos)
        vc = _update_cache(cache["v"], v, cache_pos)
        kc = shard(kc, "batch", "kv_seq", None, None)
        vc = shard(vc, "batch", "kv_seq", None, None)
        new_cache = {"k": kc, "v": vc}
        mesh = _current_mesh()
        use_flash_decode = (
            x.shape[1] == 1 and mesh is not None
            and "model" in mesh.axis_names and mesh.shape["model"] > 1
            and not cfg.dp_over_model
            and kc.shape[1] % mesh.shape["model"] == 0)
        if use_flash_decode:
            # q is tiny at decode — replicate it over 'model' and combine
            # shard-local partial softmaxes explicitly.  Leaving this to the
            # SPMD partitioner all-gathers the whole K/V cache per layer
            # (measured 2 x 34 GB/step for llama3 decode_32k; §Perf pair 3).
            q = shard(q, "batch", None, None, None)
            out = _sharded_decode_attention(
                q, kc, vc, h, q_offset=cache_pos,
                kv_valid_len=kv_valid_len if kv_valid_len is not None
                else cache_pos + 1, mesh=mesh)
        else:
            k_full = _repeat_kv(kc.astype(q.dtype), h)
            v_full = _repeat_kv(vc.astype(q.dtype), h)
            out = naive_attention(q, k_full, v_full, causal=True,
                                  q_offset=cache_pos, kv_valid_len=kv_valid_len)
    else:
        k = _repeat_kv(k, h)
        v = _repeat_kv(v, h)
        k = shard(k, "batch", None, "heads", "head_dim")
        v = shard(v, "batch", None, "heads", "head_dim")
        out = _mixed_attention(q, k, v, causal=True)
    out = shard(out, "batch", None, "heads", "head_dim")
    out = _out_proj(params, out, cfg)
    out = shard(out, "batch", None, None)
    return out, new_cache


def _out_proj(params, attn_out, cfg):
    """(B,S,H,hd) x (H,hd,D) -> (B,S,D).

    Under a backend/plan scope the contraction is routed through ``dense``
    as the flattened (H*hd, D) GEMM so the output projection is a plannable
    site (``…/attn/wo``) and contracts on the scoped engine; the float path
    keeps the original einsum (identical math, unchanged sharding).
    """
    wo = params["wo"]
    from repro.backends import runtime as backend_runtime
    if backend_runtime.active_execution() is not None:
        h, hd, d = wo.shape
        x2 = attn_out.reshape(*attn_out.shape[:-2], h * hd)
        return dense(wo.reshape(h * hd, d), x2, cfg, name="wo")
    with jax.named_scope("wo"):
        return jnp.einsum("bshd,hde->bse", attn_out,
                          wo.astype(attn_out.dtype))


def _mla_fwd(params, x, cfg, *, positions, cache, cache_pos, kv_valid_len):
    m = cfg.mla
    h = cfg.num_heads
    # Query path: low-rank down -> norm -> up, split nope/rope.
    cq = rmsnorm(params["q_norm"], dense(params["w_dq"], x, cfg, name="w_dq"),
                 cfg.rms_eps)
    q = dense(params["w_uq"], cq, cfg, name="w_uq")    # (B,S,H,nope+rope)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    q_rope = rope_lib.apply_rope(q_rope, positions, cfg.rope_theta)

    # KV latent path.
    ckv = rmsnorm(params["kv_norm"],
                  dense(params["w_dkv"], x, cfg, name="w_dkv"), cfg.rms_eps)
    krope = dense(params["w_kr"], x, cfg, name="w_kr")[:, :, None, :]  # (B,S,1,rd)
    krope = rope_lib.apply_rope(krope, positions, cfg.rope_theta)[:, :, 0]

    if cache is not None:
        ckv_c = _update_cache(cache["ckv"], ckv, cache_pos)
        krope_c = _update_cache(cache["krope"], krope, cache_pos)
        ckv_c = shard(ckv_c, "batch", "kv_seq", None)
        krope_c = shard(krope_c, "batch", "kv_seq", None)
        new_cache = {"ckv": ckv_c, "krope": krope_c}
        mesh = _current_mesh()
        use_flash_decode = (
            x.shape[1] == 1 and mesh is not None
            and "model" in mesh.axis_names and mesh.shape["model"] > 1
            and not cfg.dp_over_model
            and ckv_c.shape[1] % mesh.shape["model"] == 0)
        if use_flash_decode:
            ctx_lat = _mla_sharded_decode(
                params, q_nope, q_rope, ckv_c.astype(q.dtype),
                krope_c.astype(q.dtype), cfg,
                q_offset=cache_pos,
                kv_valid_len=kv_valid_len if kv_valid_len is not None
                else cache_pos + 1, mesh=mesh)
            out = jnp.einsum("bqhr,rhv->bqhv", ctx_lat,
                             params["w_uv"].astype(ctx_lat.dtype))
        else:
            out = _mla_absorbed_attend(params, q_nope, q_rope,
                                       ckv_c.astype(q.dtype),
                                       krope_c.astype(q.dtype),
                                       cfg, kv_valid_len, q_offset=cache_pos)
    else:
        new_cache = None
        # Train/prefill: materialize per-head K/V from the latent.
        k_nope = dense(params["w_uk"], ckv, cfg, name="w_uk")  # (B,S,H,nope)
        vfull = dense(params["w_uv"], ckv, cfg, name="w_uv")   # (B,S,H,vd)
        kr = jnp.broadcast_to(krope[:, :, None, :],
                              (*krope.shape[:2], h, m.rope_head_dim))
        k = jnp.concatenate([k_nope, kr], axis=-1)
        q_all = jnp.concatenate([q_nope, q_rope], axis=-1)
        q_all = shard(q_all, "batch", None, "heads", "head_dim")
        k = shard(k, "batch", None, "heads", "head_dim")
        vfull = shard(vfull, "batch", None, "heads", "head_dim")
        out = _mixed_attention(q_all, k, vfull, causal=True)
    out = shard(out, "batch", None, "heads", "head_dim")
    out = _out_proj(params, out, cfg)
    out = shard(out, "batch", None, None)
    return out, new_cache


def _mla_sharded_decode(params, q_nope, q_rope, ckv, krope, cfg, *,
                        q_offset, kv_valid_len, mesh):
    """Flash-decoding for MLA: absorbed scoring against the seq-sharded
    latent cache inside shard_map, log-sum-exp combine (see
    _sharded_decode_attention — same SPMD-partitioner limitation).

    Returns the combined latent context (B, Sq, H, rank); the caller applies
    W_uv outside the shard_map.
    """
    from jax.sharding import PartitionSpec as P
    from repro.models.common import shardable_batch_axes
    m = cfg.mla
    d_qk = m.nope_head_dim + m.rope_head_dim
    baxes = shardable_batch_axes(mesh, q_nope.shape[0],
                                 candidates=("pod", "data"))
    n_model = mesh.shape["model"]
    s_local = ckv.shape[1] // n_model
    # absorb W_uk into the query once, outside the shard_map
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope,
                       params["w_uk"].astype(q_nope.dtype))

    def block(ql, qr, ckv_b, kr_b, q_off, valid):
        rank = lax.axis_index("model")
        s_lat = jnp.einsum("bqhr,bkr->bhqk", ql, ckv_b)
        s_rope = jnp.einsum("bqhd,bkd->bhqk", qr, kr_b)
        s = (s_lat + s_rope).astype(jnp.float32) / jnp.sqrt(jnp.float32(d_qk))
        sq = ql.shape[1]
        qpos = jnp.arange(sq)[:, None] + q_off
        kpos = rank * s_local + jnp.arange(s_local)[None, :]
        mask = (qpos >= kpos) & (kpos < valid)
        s = jnp.where(mask[None, None], s, -1e30)
        mx = jnp.max(s, axis=-1)
        p = jnp.exp(s - mx[..., None])
        l = jnp.sum(p, axis=-1)
        ctx = jnp.einsum("bhqk,bkr->bhqr", p.astype(ckv_b.dtype), ckv_b)
        m_g = lax.pmax(mx, "model")
        alpha = jnp.exp(mx - m_g)
        l_g = lax.psum(l * alpha, "model")
        ctx_g = lax.psum(ctx * alpha[..., None].astype(ctx.dtype), "model")
        out = ctx_g / jnp.maximum(l_g[..., None], 1e-30).astype(ctx_g.dtype)
        return out.transpose(0, 2, 1, 3)                 # (B,Sq,H,rank)

    fn = jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(baxes), P(baxes), P(baxes, "model"), P(baxes, "model"),
                  P(), P()),
        out_specs=P(baxes),
        check_vma=False)
    return fn(q_lat, q_rope, ckv, krope,
              jnp.asarray(q_offset, jnp.int32),
              jnp.asarray(kv_valid_len, jnp.int32).reshape(()))


def _mla_absorbed_attend(params, q_nope, q_rope, ckv, krope, cfg, kv_valid_len,
                         q_offset=0):
    """Absorbed-decode MLA: score and read directly in the latent space.

    scores = (q_nope @ W_uk) . ckv + q_rope . krope ;  out_h = (attn @ ckv) @ W_uv
    Cache stays (B, S, rank+rd) — no per-head K/V materialization.
    """
    m = cfg.mla
    d_qk = m.nope_head_dim + m.rope_head_dim
    # (B,Sq,H,nope) x (rank,H,nope) -> (B,Sq,H,rank)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, params["w_uk"].astype(q_nope.dtype))
    s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, krope)
    scores = (s_lat + s_rope).astype(jnp.float32) / jnp.sqrt(jnp.float32(d_qk))
    sq, sk = q_nope.shape[1], ckv.shape[1]
    qpos = jnp.arange(sq)[:, None] + q_offset
    kpos = jnp.arange(sk)[None, :]
    causal = (qpos >= kpos)[None, None]
    scores = jnp.where(causal, scores, -1e30)
    if kv_valid_len is not None:
        valid = jnp.arange(sk)[None, :] < jnp.asarray(kv_valid_len).reshape(-1, 1)
        scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    ctx_lat = jnp.einsum("bhqk,bkr->bqhr", w, ckv)       # (B,Sq,H,rank)
    return jnp.einsum("bqhr,rhv->bqhv", ctx_lat, params["w_uv"].astype(ctx_lat.dtype))
