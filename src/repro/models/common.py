"""Shared modeling primitives: sharding helper, param definitions, dense
layers (with optional unary-backend quantized execution), norms, embeddings.

Parameters are plain pytrees (dicts of arrays).  Every parameter is declared
through a ``ParamDef`` carrying its *logical axes*; one walk materializes
init values, another produces `PartitionSpec`s for pjit — keeping init and
sharding definitions in one place (MaxText-style logical axis rules).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import packing
from repro.core.quantization import Quantized, quantize, quantize_per_row
from repro.models.config import ModelConfig

__all__ = [
    "ParamDef", "init_tree", "pspec_tree", "DEFAULT_RULES",
    "shard", "dense", "rmsnorm", "RMS_SCALE_INIT",
    "embed_lookup", "logits_from_embedding", "dtype_of",
    "activation_scaling", "activation_scale_mode",
]

# ---------------------------------------------------------------------------
# Logical axis rules
# ---------------------------------------------------------------------------

# logical axis name -> mesh axis (or tuple) — the single source of sharding
# truth.  The distribution layer can override (e.g. add "pod" to batch).
DEFAULT_RULES: dict[str, object] = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": "model",        # decode-time KV cache sequence sharding
    "embed": None,
    "fsdp_embed": "data",     # embed axis when cfg.fsdp is on
    "heads": "model",
    "qkv": None,
    "kv_heads": None,          # kv heads usually < model-axis size: replicate
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "layers": None,
    "conv": None,
    "state": None,
    "lora": None,
}


def rules_for(cfg: ModelConfig) -> dict[str, object]:
    rules = dict(DEFAULT_RULES)
    if cfg.fsdp:
        rules["embed"] = "data"
    if cfg.dp_over_model:
        # archs whose heads don't divide the model axis (rwkv6: 40 heads,
        # musicgen: 24) run pure data parallelism across the WHOLE mesh
        # (batch also sharded over 'model') with FSDP for weight memory —
        # no tensor parallelism, no redundant compute.  'pod' is LAST so the
        # divisibility filter spends the global batch on data x model first
        # (batch 256 = 16 x 16 exactly; on the 512-chip mesh the pod axis
        # replicates rather than idling the model axis).
        rules["batch"] = ("data", "model", "pod")
        rules["heads"] = None
        rules["mlp"] = None
        rules["vocab"] = None
    return rules


# Thread-local logical-rule overrides (e.g. batch=() when the global batch is
# too small to shard over the data axes — long_500k has batch 1).  Entered by
# the step factories during tracing so in-model shard() calls agree with the
# jit in_shardings.
import contextlib
import threading

_TLS = threading.local()


@contextlib.contextmanager
def rule_overrides(**kw):
    prev = getattr(_TLS, "overrides", {})
    _TLS.overrides = {**prev, **kw}
    try:
        yield
    finally:
        _TLS.overrides = prev


def _active_overrides() -> dict:
    return getattr(_TLS, "overrides", {})


def shardable_batch_axes(mesh, batch_size: int,
                         candidates=("pod", "data")) -> tuple[str, ...]:
    """Longest prefix of batch axes whose product divides batch_size."""
    if isinstance(candidates, str):
        candidates = (candidates,)
    keep: list[str] = []
    prod = 1
    for a in candidates or ():
        if a in mesh.axis_names and batch_size % (prod * mesh.shape[a]) == 0:
            keep.append(a)
            prod *= mesh.shape[a]
    return tuple(keep)


def _mesh_axes_present() -> tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    return () if mesh.empty else tuple(mesh.axis_names)


def logical_to_pspec(logical: tuple[str | None, ...],
                     rules: dict[str, object],
                     mesh_axes: tuple[str, ...],
                     shape: tuple[int, ...] | None = None,
                     mesh_shape: dict[str, int] | None = None) -> P:
    """Map logical axis names to a PartitionSpec.

    When ``shape`` + ``mesh_shape`` are provided, mesh axes whose size does
    not divide the corresponding dim are dropped (e.g. 40 RWKV heads or 24
    musicgen heads on a 16-way model axis fall back to replication; batch=1
    long_500k cells fall back to unsharded batch).
    """
    rules = {**rules, **_active_overrides()}
    spec = []
    used: set[str] = set()
    for i, name in enumerate(logical):
        axis = rules.get(name) if name else None
        if axis is None:
            spec.append(None)
            continue
        axes = tuple(a for a in (axis if isinstance(axis, (tuple, list))
                                 else (axis,))
                     if a in mesh_axes and a not in used)
        if shape is not None and mesh_shape is not None:
            kept = []
            prod = 1
            for a in axes:
                if shape[i] % (prod * mesh_shape[a]) == 0:
                    kept.append(a)
                    prod *= mesh_shape[a]
            axes = tuple(kept)
        used.update(axes)
        if not axes:
            spec.append(None)
        elif len(axes) == 1:
            spec.append(axes[0])
        else:
            spec.append(axes)
    return P(*spec)


def shard(x: jax.Array, *logical: str | None,
          rules: dict[str, object] | None = None) -> jax.Array:
    """Constrain ``x``'s sharding by logical axis names (no-op outside a
    ``jax.set_mesh`` context)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.axis_names:
        return x
    rules = DEFAULT_RULES if rules is None else rules
    spec = logical_to_pspec(tuple(logical), rules, tuple(mesh.axis_names),
                            shape=tuple(x.shape),
                            mesh_shape=dict(mesh.shape))
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter definitions
# ---------------------------------------------------------------------------

RMS_SCALE_INIT = "ones"


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    init: str = "lecun"           # lecun | zeros | ones | normal(σ=0.02) | ssm_a | ssm_dt
    fan_in_axes: tuple[int, ...] = (0,)

    def materialize(self, key: jax.Array, dtype) -> jax.Array:
        if self.init == "zeros":
            return jnp.zeros(self.shape, dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, dtype)
        if self.init == "normal":
            return (0.02 * jax.random.normal(key, self.shape)).astype(dtype)
        if self.init == "ssm_a":
            # A_log init: log of [1, 16] range over heads (Mamba2 convention);
            # broadcast across any leading (stacked-layer) axes.
            base = jnp.log(jnp.linspace(1.0, 16.0, self.shape[-1]))
            return jnp.broadcast_to(base, self.shape).astype(dtype)
        if self.init == "ssm_dt":
            # dt bias ~ softplus-inv of log-uniform dt in [1e-3, 1e-1]
            u = jax.random.uniform(key, self.shape)
            dt = jnp.exp(u * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
            return jnp.log(jnp.expm1(dt)).astype(dtype)
        fan_in = 1
        for a in self.fan_in_axes:
            fan_in *= self.shape[a]
        scale = 1.0 / math.sqrt(max(fan_in, 1))
        return (scale * jax.random.normal(key, self.shape)).astype(dtype)


def init_tree(defs, key: jax.Array, dtype) -> dict:
    """Materialize a (nested dict) tree of ParamDefs with split keys."""
    leaves, treedef = jax.tree_util.tree_flatten(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    vals = [d.materialize(k, dtype) for d, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def pspec_tree(defs, rules: dict[str, object], mesh_axes: tuple[str, ...],
               mesh_shape: dict[str, int] | None = None):
    return jax.tree_util.tree_map(
        lambda d: logical_to_pspec(d.logical, rules, mesh_axes,
                                   shape=d.shape, mesh_shape=mesh_shape),
        defs, is_leaf=lambda x: isinstance(x, ParamDef))


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Activation quantization granularity (backend-execution scopes)
# ---------------------------------------------------------------------------

#: Granularities ``_backend_matmul`` accepts for the activation operand.
_ACT_SCALE_MODES = ("per-tensor", "per-row")


@contextlib.contextmanager
def activation_scaling(mode: str):
    """Select the activation quantization granularity for backend execution.

    ``"per-tensor"`` (default) — one absmax scale across the whole
    activation batch, the paper's INT-inference convention; co-batched rows
    share a grid, so a request's integer codes depend on its batchmates.
    ``"per-row"`` — one scale per activation row, making each co-batched
    request's codes a pure function of its own tokens (the property the
    serving engine's identical-token-stream check needs to be a *strict*
    gate under backend execution).  Read at trace time, like the backend
    scopes — trace jitted steps inside the context.
    """
    if mode not in _ACT_SCALE_MODES:
        raise ValueError(f"activation scaling mode must be one of "
                         f"{_ACT_SCALE_MODES}, got {mode!r}")
    prev = getattr(_TLS, "act_scale", "per-tensor")
    _TLS.act_scale = mode
    try:
        yield
    finally:
        _TLS.act_scale = prev


def activation_scale_mode() -> str:
    """The granularity ``_backend_matmul`` quantizes activations at now."""
    return getattr(_TLS, "act_scale", "per-tensor")


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def dense(w: jax.Array, x: jax.Array, cfg: ModelConfig | None = None,
          out_logical: tuple[str | None, ...] | None = None,
          name: str | None = None) -> jax.Array:
    """x @ w with optional unary-backend quantized execution.

    ``name`` — the weight's parameter-tree leaf key (``"wq"``, ``"w_up"``…).
    Combined with the live ``repro.backends.site_scope`` stack it forms the
    GEMM's *site name* (``"layers/attn/wq"``), which per-site backend plans
    match against; see the naming contract in ``repro.backends.runtime``.

    Execution precedence:

    1. An active ``repro.backends.use_backend(...)`` / ``use_plan(...)``
       scope — the scope names the backend for this site (a plan may name
       none, falling through to the float path); both operands are quantized
       to the backend's bit-width and the int tiles are contracted on the
       backend engine (simulator or Pallas kernel), then dequantized back to
       the activation dtype.  The scope is read at trace time; see
       ``repro.backends.runtime`` for the jit caveat.
    2. ``cfg.quant_kernel`` — the Pallas packed-integer kernel (the paper's
       PE array stand-in).  tuGEMM/tubGEMM/bGEMM are numerically identical
       (deterministic integer GEMM); uGEMM adds its stochastic multiplier
       error via the LUT path.
    3. The plain float matmul (default).

    A named site also enters ``jax.named_scope(name)``, so the HLO's
    ``op_name`` carries the whole site path and a device trace can charge
    each GEMM to its site.
    """
    with jax.named_scope(name) if name else contextlib.nullcontext():
        return _dense(w, x, cfg, name)


def _dense(w: jax.Array, x: jax.Array, cfg: ModelConfig | None,
           name: str | None) -> jax.Array:
    from repro.backends import runtime as backend_runtime
    execution = backend_runtime.active_execution()
    if execution is not None:
        site = backend_runtime.current_site(name)
        backend = execution.backend_for(site)
        if backend is not None:
            return _backend_matmul(execution, backend, site, w, x)
        k = w.shape[0]
        execution.observe(site, m=math.prod(x.shape[:-1]), k=k,
                          n_out=w.size // k)
        # A live scope owns execution: sites its plan leaves unmatched run
        # FLOAT (the documented contract) — never the cfg.quant_kernel path,
        # which would silently mix a second quantization scheme into the
        # plan's drift/bit-exactness evidence.
        return _plain_matmul(x, w)
    if cfg is not None and cfg.quant_bits is not None and cfg.quant_kernel:
        if packing.is_packed(w):
            raise TypeError(
                "cfg.quant_kernel re-quantizes at cfg.quant_bits, which "
                "would round already-packed codes a second time — execute "
                "packed stores under use_backend/use_plan at the store's "
                "width, or keep float parameters for the quant-kernel path")
        from repro.kernels import ops as kops
        w2 = w.reshape(w.shape[0], -1) if w.ndim > 2 else w
        wq = quantize(w2.astype(jnp.float32), bits=cfg.quant_bits)
        if cfg.quant_backend == "ugemm":
            from repro.core import gemm_sims
            xq = quantize(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                          bits=cfg.quant_bits, per_channel=False)
            out = gemm_sims.ugemm_exact(xq.values, wq.values, bits=cfg.quant_bits)
            out = (out * xq.scale * wq.scale.reshape(1, -1)).astype(x.dtype)
        else:
            out = kops.quantized_matmul(x, wq, act_bits=min(cfg.quant_bits * 2, 8))
        return out.reshape(*x.shape[:-1], *w.shape[1:])
    return _plain_matmul(x, w)


def _backend_matmul(execution, backend, site: str, w: jax.Array,
                    x: jax.Array) -> jax.Array:
    """Contract ``x @ w`` on ``backend`` (the scope's choice for ``site``)
    as integer tiles.

    Both operands are quantized at the backend's bit-width — the hardware
    units consume w-bit codes on both ports — weights per output channel,
    activations per tensor by default or per row under
    ``activation_scaling("per-row")``; the integer result is rescaled by
    both quantization scales and cast back to the activation dtype.  The
    activation streams as the temporal operand (orientation does not change
    the integer result; cycle accounting prices the weight-streamed
    schedule, see ``launch/serve.py``).

    A :class:`repro.core.packing.PackedQuantized` weight skips the weight
    quantize: its store holds exactly the codes and scales ``quantize``
    would produce at pack time, so the execute + rescale recipe below is
    bit-identical to the float-leaf path — *iff* the store's width matches
    the backend's.  A mismatch is the stale-weight hazard (the codes were
    rounded for a different grid) and raises rather than re-quantizing.
    """
    x2 = x.reshape(-1, x.shape[-1])
    if packing.is_packed(w):
        if int(w.bits) != int(backend.bits):
            raise ValueError(
                f"site {site!r}: packed store holds {w.bits}-bit codes but "
                f"the backend executes at {backend.bits}-bit — re-quantizing "
                f"packed codes at a second width compounds quantization "
                f"error; repack from the float parameters with "
                f"backends.pack_weights (packed-width-mismatch)")
        wq = w.quantized()  # exact pack-time codes (k, n) + per-channel scale
        k, n_out = w.k, w.n_out
    else:
        w2 = w.reshape(w.shape[0], -1) if w.ndim > 2 else w
        wq = quantize(w2.astype(jnp.float32), bits=backend.bits)
        k, n_out = w2.shape[0], w2.shape[1]
    if activation_scale_mode() == "per-row":
        xq = quantize_per_row(x2.astype(jnp.float32), bits=backend.bits)
    else:
        xq = quantize(x2.astype(jnp.float32), bits=backend.bits,
                      per_channel=False)
    out = backend.execute(xq.values, wq.values)
    # Apply the two dequant scales sequentially (one multiply per port)
    # rather than pre-multiplying them: the pre-product `xq.scale * wq.scale`
    # is not bit-stable under XLA when one operand chain is a baked constant
    # (a packed store's scales) and the other is computed in-graph, which
    # would break packed-vs-float bit-identity by 1-2 ulp inside scanned
    # layers.  Sequential application compiles identically for both.
    out = out.astype(jnp.float32) * xq.scale * wq.scale.reshape(1, -1)
    execution.record(site, m=x2.shape[0], k=k, n_out=n_out, backend=backend)
    return out.astype(x.dtype).reshape(*x.shape[:-1], *w.shape[1:])


def _plain_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    if packing.is_packed(w):
        # Float path over a packed leaf (e.g. a plan leaving this site
        # unmatched): dequantize the stored codes — the only float matrix
        # the codes can honestly reconstruct.
        w = w.dequantize()
    wshape = w.shape
    w2 = w.reshape(wshape[0], -1)
    y = jnp.matmul(x, w2.astype(x.dtype))
    return y.reshape(*x.shape[:-1], *wshape[1:])


@jax.custom_vjp
def bf16_grad(x: jax.Array) -> jax.Array:
    """Identity whose cotangent is rounded through bf16.

    Placed at block boundaries so the backward tensor-parallel all-reduces of
    activation gradients run at bf16 instead of f32 (the f32 comes from the
    norm layers' f32 internals) — halves the dominant collective term of
    TP-heavy training cells (§Perf pair 2).  Gradient noise added: one bf16
    rounding per block boundary, far below optimizer noise floor.
    """
    return x


def _bf16_grad_fwd(x):
    return x, None


def _bf16_grad_bwd(_, g):
    return (g.astype(jnp.bfloat16).astype(g.dtype),)


bf16_grad.defvjp(_bf16_grad_fwd, _bf16_grad_bwd)


def rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-5,
            gemma_style: bool = False) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    s = scale.astype(jnp.float32)
    y = y * (1.0 + s) if gemma_style else y * s
    return y.astype(dt)


def embed_lookup(table: jax.Array, ids: jax.Array, compute_dtype) -> jax.Array:
    return jnp.take(table, ids, axis=0).astype(compute_dtype)


def logits_from_embedding(table: jax.Array, x: jax.Array,
                          softcap: float | None = None) -> jax.Array:
    logits = jnp.matmul(x, jnp.swapaxes(table.astype(x.dtype), 0, 1))
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits
