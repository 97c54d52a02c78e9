"""Mamba2 + attention hybrid: a published pattern of Mamba2 and NoPE GQA
layers, each followed by a SwiGLU MLP, with a tied head (granite-4.0-h).

The float32 forward pass: embedding times ``embedding_multiplier``; per
layer ``x + r * mixer(RMSNorm(x))`` then ``x + r * MLP(RMSNorm(x))`` with
``r`` the ``residual_multiplier``; final norm; the tied head's logits
divided by ``logits_scaling``.  A Mamba2 mixer: projections to z, x, B, C
and dt; a causal depthwise conv (with bias) and SiLU over x, B and C;
``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``; the selective scan
``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
computed in chunks of 64 positions (``ssd``); a gated RMSNorm ``RMSNorm(y * silu(z))``; the
output projection.  An attention mixer: causal grouped-query attention
with no position embedding, scores scaled by ``attention_multiplier``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness.reference import HI, mm, rmsnorm, round_fp8

# published config.json key -> ModelConfig field
CONFIG_KEYS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "shared_intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_multiplier": "attn_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "mamba_d_state": "ssm.state_dim",
    "mamba_d_head": "ssm.head_dim",
    "mamba_expand": "ssm.expand",
    "mamba_n_groups": "ssm.n_groups",
    "mamba_d_conv": "ssm.conv_kernel",
    "mamba_chunk_size": "ssm.chunk",
}
#: what the program computes, against the published flags that name it
_FLAGS = {"hidden_act": "silu", "position_embedding_type": "nope",
          "mamba_conv_bias": True, "mamba_proj_bias": False,
          "attention_bias": False, "normalization_function": "rmsnorm",
          "num_local_experts": 0, "tie_word_embeddings": True}
_MAMBA_MATRICES = ("w_z", "w_x", "w_b", "w_c", "w_dt", "out_proj")
_FP32 = 4  # bytes of the SSM state and conv tails as the program holds them


@dataclasses.dataclass(frozen=True)
class Dims:
    """The model's sizes, as the plain reference reads them."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rms_eps: float
    layer_types: tuple[str, ...]
    attn_layers: int
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    n_groups: int
    conv_kernel: int
    attn_scale: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @property
    def mamba_layers(self) -> int:
        return self.layers - self.attn_layers

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        for key, want in _FLAGS.items():
            if c.get(key) != want:
                raise ValueError(f"the reference covers {key}={want!r}, "
                                 f"the config has {c.get(key)!r}")
        types = tuple(c["layer_types"])
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["shared_intermediate_size"], vocab=c["vocab_size"],
                   rms_eps=float(c["rms_norm_eps"]), layer_types=types,
                   attn_layers=types.count("attention"),
                   ssm_heads=c["mamba_n_heads"],
                   ssm_head_dim=c["mamba_d_head"],
                   d_state=c["mamba_d_state"], n_groups=c["mamba_n_groups"],
                   conv_kernel=c["mamba_d_conv"],
                   attn_scale=float(c["attention_multiplier"]),
                   embedding_multiplier=float(c["embedding_multiplier"]),
                   residual_multiplier=float(c["residual_multiplier"]),
                   logits_scaling=float(c["logits_scaling"]))

    @classmethod
    def from_model(cls, cfg) -> "Dims":
        s = cfg.ssm
        types = tuple(cfg.layer_types)
        return cls(layers=cfg.num_layers, d_model=cfg.d_model,
                   heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
                   head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
                   vocab=cfg.vocab_size, rms_eps=cfg.rms_eps,
                   layer_types=types, attn_layers=types.count("attention"),
                   ssm_heads=s.expand * cfg.d_model // s.head_dim,
                   ssm_head_dim=s.head_dim, d_state=s.state_dim,
                   n_groups=s.n_groups, conv_kernel=s.conv_kernel,
                   attn_scale=float(cfg.attn_scale),
                   embedding_multiplier=float(cfg.embedding_multiplier),
                   residual_multiplier=float(cfg.residual_multiplier),
                   logits_scaling=float(cfg.logits_scaling))


def check(cfg, config: dict) -> None:
    if cfg.layer_types is None or cfg.ssm is None or cfg.attention != "gqa" \
            or cfg.is_moe:
        raise ValueError(f"{cfg.arch_id}: the registry holds no layer "
                         "pattern of Mamba2 and GQA layers")
    if tuple(config["layer_types"]) != cfg.layer_types \
            or len(cfg.layer_types) != cfg.num_layers:
        raise ValueError(f"{cfg.arch_id}: layer_types differ from the "
                         "config file")
    if config["intermediate_size"] != config["shared_intermediate_size"] \
            or cfg.position_embedding != config["position_embedding_type"] \
            or cfg.activation != "swiglu" \
            or cfg.resolved_head_dim * cfg.num_heads != cfg.d_model \
            or cfg.ssm.expand * cfg.d_model != \
            config["mamba_n_heads"] * config["mamba_d_head"]:
        raise ValueError(f"{cfg.arch_id}: MLP width, position embedding, "
                         "activation or a head width differs from the "
                         "config file")


def shapes(d: Dims) -> dict:
    """Leaf shape and fan-in of every weight, as the engine lays them out.

    Kinds where a leaf is no matrix: ``a_log`` normal with variance 1/64
    (A = -exp(a_log) near -1), ``dt_bias`` unit normal, ``d_skip`` and the
    gated norm ``norm``-kind (near 1), conv filters and biases normal with
    variance 1/kernel.  The embedding, which is also the head, is normal
    with variance 1/vocab.
    """
    m, a, D, F = d.mamba_layers, d.attn_layers, d.d_model, d.d_ff
    H, P, K = d.ssm_heads, d.ssm_head_dim, d.conv_kernel
    di, gn = d.d_inner, d.n_groups * d.d_state

    def mlp(n):
        return {"w_gate": ((n, D, F), D), "w_up": ((n, D, F), D),
                "w_down": ((n, F, D), F)}

    def norms(n):
        return {"ln1": ((n, D), "norm"), "ln2": ((n, D), "norm")}

    return {
        "embed": ((d.vocab, D), d.vocab),
        "final_norm": ((D,), "norm"),
        "layers": {
            "mamba": {**norms(m), "mlp": mlp(m), "ssm": {
                "w_z": ((m, D, di), D), "w_x": ((m, D, di), D),
                "w_b": ((m, D, gn), D), "w_c": ((m, D, gn), D),
                "w_dt": ((m, D, H), D),
                "conv_x_w": ((m, K, di), K), "conv_x_b": ((m, di), K),
                "conv_bc_w": ((m, K, 2 * gn), K),
                "conv_bc_b": ((m, 2 * gn), K),
                "a_log": ((m, H), 64), "dt_bias": ((m, H), None),
                "d_skip": ((m, H), "norm"), "norm": ((m, di), "norm"),
                "out_proj": ((m, di, D), di)}},
            "attention": {**norms(a), "mlp": mlp(a), "attn": {
                "wq": ((a, D, d.heads, d.head_dim), D),
                "wk": ((a, D, d.kv_heads, d.head_dim), D),
                "wv": ((a, D, d.kv_heads, d.head_dim), D),
                "wo": ((a, d.heads, d.head_dim, D), d.heads * d.head_dim)}},
        },
    }


# --- the forward pass -------------------------------------------------------

def _conv(seq, w, b):
    """Causal depthwise conv of (S, C) with filters (K, C), then SiLU."""
    k = w.shape[0]
    pad = jnp.pad(seq, ((k - 1, 0), (0, 0)))
    out = sum(pad[i:i + seq.shape[0]] * w[i] for i in range(k))
    return jax.nn.silu(out + b)


#: positions per chunk of the reference's SSD
CHUNK = 64


def ssd(x, dt, a, b, c):
    """The selective scan over (S, H, P) inputs, plainly chunked.

    ``h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t`` from
    a zero state, with ``dt`` (S, H), ``a`` (H,), ``b`` and ``c`` (S, H,
    N).  Inside a chunk every output is the sum over its chunk's earlier
    positions, each weighted by the decay between the two; across chunks
    the state at each chunk's end is carried one chunk at a time.
    """
    s, h, p = x.shape
    q = min(CHUNK, s)
    n = -(-s // q)
    pad = n * q - s
    x, dt, b, c = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                   for v in (x, dt, b, c))
    log_decay = jnp.cumsum((dt * a).reshape(n, q, h), axis=1)   # (n, q, H)
    causal = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
    gap = log_decay[:, :, None] - log_decay[:, None]             # t minus s
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
    bq, cq = b.reshape(n, q, h, -1), c.reshape(n, q, h, -1)
    xdt = (x * dt[..., None]).reshape(n, q, h, p)
    scores = jnp.einsum("nthk,nshk->ntsh", cq, bq, precision=HI) * decay
    y = jnp.einsum("ntsh,nshp->nthp", scores, xdt, precision=HI)
    to_end = jnp.exp(log_decay[:, -1:] - log_decay)
    ends = jnp.einsum("nsh,nshk,nshp->nhkp", to_end, bq, xdt, precision=HI)

    def carry(state, inp):
        end, total = inp
        return state * jnp.exp(total)[:, None, None] + end, state

    _, before = jax.lax.scan(
        carry, jnp.zeros((h, b.shape[-1], p), jnp.float32),
        (ends, log_decay[:, -1]))
    y = y + jnp.einsum("nthk,nhkp->nthp", cq * jnp.exp(log_decay)[..., None],
                       before, precision=HI)
    return y.reshape(n * q, h, p)[:s]


def mamba(d, p, x):
    """One Mamba2 mixer over (S, D), float32."""
    s = x.shape[0]
    H, P, N, G = d.ssm_heads, d.ssm_head_dim, d.d_state, d.n_groups
    z = mm(x, p["w_z"])
    xs = _conv(mm(x, p["w_x"]), p["conv_x_w"], p["conv_x_b"])
    bc = _conv(jnp.concatenate([mm(x, p["w_b"]), mm(x, p["w_c"])], -1),
               p["conv_bc_w"], p["conv_bc_b"])
    rep = H // G
    b = jnp.repeat(bc[:, :G * N].reshape(s, G, N), rep, axis=1)
    c = jnp.repeat(bc[:, G * N:].reshape(s, G, N), rep, axis=1)
    dt = jax.nn.softplus(mm(x, p["w_dt"]) + p["dt_bias"])        # (S, H)
    xh = xs.reshape(s, H, P)
    y = ssd(xh, dt, -jnp.exp(p["a_log"]), b, c)
    y = (y + p["d_skip"][:, None] * xh).reshape(s, H * P)
    return mm(rmsnorm(p["norm"], y * jax.nn.silu(z), d.rms_eps),
              p["out_proj"])


def attention(d, p, x):
    """Causal grouped-query attention over (S, D), no position embedding."""
    s = x.shape[0]
    q = mm(x, p["wq"]).reshape(s, d.heads, d.head_dim)
    k = mm(x, p["wk"]).reshape(s, d.kv_heads, d.head_dim)
    v = mm(x, p["wv"]).reshape(s, d.kv_heads, d.head_dim)
    group = d.heads // d.kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k, precision=HI) * d.attn_scale
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    ctx = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, -1), v,
                     precision=HI)
    return mm(ctx.reshape(s, -1), p["wo"])


def _layer(d, fp8, kind, x, lp):
    """One layer: its mixer, then its MLP, each residual scaled."""
    lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
    mixer_key, mixer = (("ssm", mamba) if kind == "mamba"
                        else ("attn", attention))
    mp, m = dict(lp[mixer_key]), dict(lp["mlp"])
    if fp8:
        mp = {k: round_fp8(v) if k in _MAMBA_MATRICES or mixer_key == "attn"
              else v for k, v in mp.items()}
        m = {k: round_fp8(v) for k, v in m.items()}
    r = d.residual_multiplier
    x = x + r * mixer(d, mp, rmsnorm(lp["ln1"], x, d.rms_eps))
    y = rmsnorm(lp["ln2"], x, d.rms_eps)
    g = jax.nn.silu(mm(y, m["w_gate"]))
    return x + r * mm(g * mm(y, m["w_up"]), m["w_down"])


def _period(types) -> int:
    n = len(types)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and tuple(types) == tuple(types[:p]) * (n // p))


@functools.partial(jax.jit, static_argnames=("d", "fp8"))
def logits(params, tokens, d, fp8: bool = False):
    """(S,) token ids -> (S, vocab) float32 next-token logits.

    The layers run period by period of ``layer_types`` (a scan over the
    periods, each period's layers one after another), each layer cast to
    float32 on its own.
    """
    table = params["embed"].astype(jnp.float32)
    x = table[tokens] * d.embedding_multiplier
    p = _period(d.layer_types)
    kinds = d.layer_types[:p]
    per = {k: kinds.count(k) for k in set(kinds)}
    reps = d.layers // p
    stacks = {k: jax.tree_util.tree_map(
        lambda w, n=n: w.reshape(reps, n, *w.shape[1:]), params["layers"][k])
        for k, n in per.items()}

    def one_period(x, lp):
        seen = dict.fromkeys(per, 0)
        for kind in kinds:
            layer = jax.tree_util.tree_map(lambda w: w[seen[kind]], lp[kind])
            seen[kind] += 1
            x = _layer(d, fp8, kind, x, layer)
        return x, None

    x, _ = jax.lax.scan(one_period, x, stacks)
    x = rmsnorm(params["final_norm"].astype(jnp.float32), x, d.rms_eps)
    head = round_fp8(table) if fp8 else table
    return mm(x, head.T) / d.logits_scaling


# --- work counts, for the cell's per-layer metrics ---------------------------

def matmul_weights(d: Dims) -> int:
    """Weights of every matrix product of one token's forward pass."""
    di, gn = d.d_inner, d.n_groups * d.d_state
    mamba_w = d.d_model * (2 * di + 2 * gn + d.ssm_heads) + di * d.d_model
    attn_w = d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    mlp_w = 3 * d.d_model * d.d_ff
    return (d.mamba_layers * mamba_w + d.attn_layers * attn_w
            + d.layers * mlp_w + d.d_model * d.vocab)


def ssm_step_flops(d: Dims, rows: int) -> int:
    """One token's recurrence in every Mamba2 layer, for ``rows`` tokens:
    the conv over x, B and C (a multiply-add per tap), the state's decay
    and input (a multiply each, an add), the readout (a multiply-add per
    state element) and the D skip."""
    conv = 2 * d.conv_kernel * (d.d_inner + 2 * d.n_groups * d.d_state)
    state = d.ssm_heads * d.d_state * d.ssm_head_dim
    return rows * d.mamba_layers * (conv + 5 * state + 2 * d.d_inner)


def ssm_state_bytes(d: Dims, rows: int) -> int:
    """One token's SSM state and conv tails in every Mamba2 layer, read and
    written, for ``rows`` tokens, at the program's float32."""
    state = d.ssm_heads * d.d_state * d.ssm_head_dim
    tails = (d.conv_kernel - 1) * (d.d_inner + 2 * d.n_groups * d.d_state)
    return 2 * rows * d.mamba_layers * (state + tails) * _FP32


def decode_step_flops(d: Dims, rows: int, ctx_tokens: int) -> int:
    """One decode step: ``rows`` tokens through every matrix product and
    every Mamba2 recurrence, plus the attention layers' scores and weighted
    values over ``ctx_tokens`` summed attended positions."""
    attn = 4 * d.attn_layers * d.heads * d.head_dim * ctx_tokens
    return 2 * matmul_weights(d) * rows + ssm_step_flops(d, rows) + attn

