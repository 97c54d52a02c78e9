"""Multi-device numerical equivalence on fake CPU meshes (subprocess — the
device count must be pinned before jax initializes).

Covers the shard_map code paths the dry-run only exercises structurally:
flash-decoding (GQA + MLA) vs the single-device oracle, expert-parallel MoE
vs the dense reference, and the int8 compressed all-reduce.
"""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.models import attention as A
from repro.models.config import ModelConfig, MLAConfig, MoEConfig

rng = np.random.default_rng(0)
mesh = make_mesh((2, 4), ("data", "model"))

# ---- 1. GQA flash-decoding vs naive oracle --------------------------------
B, S, H, KVH, D = 4, 32, 8, 2, 16
q = jnp.asarray(rng.normal(0, 1, (B, 1, H, D)), jnp.float32)
kc = jnp.asarray(rng.normal(0, 1, (B, S, KVH, D)), jnp.float32)
vc = jnp.asarray(rng.normal(0, 1, (B, S, KVH, D)), jnp.float32)
pos = 19  # only the first pos+1 cache slots are valid

with jax.set_mesh(mesh):
    q_s = jax.device_put(q, NamedSharding(mesh, P("data")))
    kc_s = jax.device_put(kc, NamedSharding(mesh, P("data", "model")))
    vc_s = jax.device_put(vc, NamedSharding(mesh, P("data", "model")))
    out = A._sharded_decode_attention(q_s, kc_s, vc_s, H, q_offset=pos,
                                      kv_valid_len=pos + 1, mesh=mesh)
kf = A._repeat_kv(kc, H)
vf = A._repeat_kv(vc, H)
want = A.naive_attention(q, kf, vf, causal=True, q_offset=pos,
                         kv_valid_len=np.full(B, pos + 1))
err = float(jnp.max(jnp.abs(out - want)))
assert err < 1e-5, f"gqa flash-decode mismatch {err}"
print("GQA_DECODE_OK", err)

# ---- 2. MLA flash-decoding vs absorbed oracle ------------------------------
cfg = ModelConfig(d_model=32, num_heads=4, num_kv_heads=4, attention="mla",
                  mla=MLAConfig(q_lora_rank=16, kv_lora_rank=8,
                                rope_head_dim=4, nope_head_dim=8, v_head_dim=8))
m = cfg.mla
params = {
    "w_uk": jnp.asarray(rng.normal(0, 0.3, (m.kv_lora_rank, 4, m.nope_head_dim)), jnp.float32),
    "w_uv": jnp.asarray(rng.normal(0, 0.3, (m.kv_lora_rank, 4, m.v_head_dim)), jnp.float32),
}
qn = jnp.asarray(rng.normal(0, 1, (B, 1, 4, m.nope_head_dim)), jnp.float32)
qr = jnp.asarray(rng.normal(0, 1, (B, 1, 4, m.rope_head_dim)), jnp.float32)
ckv = jnp.asarray(rng.normal(0, 1, (B, S, m.kv_lora_rank)), jnp.float32)
kr = jnp.asarray(rng.normal(0, 1, (B, S, m.rope_head_dim)), jnp.float32)
with jax.set_mesh(mesh):
    ckv_s = jax.device_put(ckv, NamedSharding(mesh, P("data", "model")))
    kr_s = jax.device_put(kr, NamedSharding(mesh, P("data", "model")))
    ctx = A._mla_sharded_decode(params, qn, qr, ckv_s, kr_s, cfg,
                                q_offset=pos, kv_valid_len=pos + 1, mesh=mesh)
    got = jnp.einsum("bqhr,rhv->bqhv", ctx, params["w_uv"])
want = A._mla_absorbed_attend(params, qn, qr, ckv, kr, cfg,
                              np.full(B, pos + 1), q_offset=pos)
err = float(jnp.max(jnp.abs(got - want)))
assert err < 1e-5, f"mla flash-decode mismatch {err}"
print("MLA_DECODE_OK", err)

# ---- 3. expert-parallel MoE (psum) vs dense reference ----------------------
from repro.models import moe as MOE
from repro.models.common import init_tree
mcfg = ModelConfig(family="moe", d_model=32, d_ff=64, vocab_size=64,
                   moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                                 capacity_factor=8.0))
mparams = init_tree(MOE.moe_defs(mcfg), jax.random.PRNGKey(1), jnp.float32)
x = jnp.asarray(rng.normal(0, 1, (2, 16, 32)), jnp.float32)
with jax.set_mesh(mesh):
    out_ep, aux = MOE.moe_fwd(mparams, x, mcfg)      # EP over model=4
out_ref, _ = MOE.moe_fwd(mparams, x, mcfg)           # no mesh -> local path
err = float(jnp.max(jnp.abs(out_ep - out_ref)))
assert err < 1e-4, f"EP-psum vs local mismatch {err}"
print("MOE_EP_OK", err)

# ---- 3b. a2a EP vs psum EP --------------------------------------------------
import dataclasses as dc
mcfg_a2a = mcfg.replace(moe=dc.replace(mcfg.moe, ep_impl="a2a"))
xa = jnp.asarray(rng.normal(0, 1, (2, 16, 32)), jnp.float32)   # T=32 >= 4*4
with jax.set_mesh(mesh):
    out_a2a, _ = MOE.moe_fwd(mparams, xa, mcfg_a2a)
    out_psum, _ = MOE.moe_fwd(mparams, xa, mcfg)
err = float(jnp.max(jnp.abs(out_a2a - out_psum)))
assert err < 1e-4, f"a2a vs psum mismatch {err}"
print("MOE_A2A_OK", err)

# ---- 4. int8 compressed all-reduce over data axis ---------------------------
from repro.optim.compression import int8_psum
g = {"w": jnp.asarray(rng.normal(0, 1, (64, 64)), jnp.float32)}
with jax.set_mesh(mesh):
    out = int8_psum(g, mesh, axis="data")
# with identical replicas the psum returns n_data * g (up to int8 rounding)
rel = float(jnp.max(jnp.abs(out["w"] - 2 * g["w"])) / jnp.max(jnp.abs(2 * g["w"])))
assert rel < 0.02, f"int8 psum rel err {rel}"
print("INT8_PSUM_OK", rel)
"""


def test_multidevice_numerics():
    # JAX_PLATFORMS=cpu: without it jax tries to initialize the TPU backend
    # (libtpu is installed in the image) and stalls for minutes before
    # falling back — the fake-device mesh only needs the CPU platform.
    # Persistent compilation cache is safe here (isolated process, no data
    # threads / donated-buffer reloads) and cuts warm reruns to seconds.
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "HOME": "/root",
           "JAX_PLATFORMS": "cpu",
           "JAX_DISABLE_MOST_OPTIMIZATIONS": "1",
           "JAX_COMPILATION_CACHE_DIR": os.path.abspath(".jax_cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}
    res = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=900, env=env)
    out = res.stdout
    for marker in ("GQA_DECODE_OK", "MLA_DECODE_OK", "MOE_EP_OK",
                   "MOE_A2A_OK", "INT8_PSUM_OK"):
        assert marker in out, f"missing {marker}\n{out}\n{res.stderr}"
