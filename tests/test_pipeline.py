"""Pipeline parallelism (GPipe over the pod axis): forward AND gradient
equivalence to the sequential reference, on a fake 4-pod mesh (subprocess —
device count must be pinned before jax initializes)."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
from repro.launch.pipeline import pipeline_apply, split_stages

rng = np.random.default_rng(0)
L, D, MB, M = 8, 16, 4, 6      # 8 layers -> 4 stages x 2; 6 microbatches of 4
ws = jnp.asarray(rng.normal(0, 0.3, (L, D, D)), jnp.float32)
bs = jnp.asarray(rng.normal(0, 0.1, (L, D)), jnp.float32)
x = jnp.asarray(rng.normal(0, 1, (M, MB, D)), jnp.float32)

def layer(w, b, h):
    return jnp.tanh(h @ w + b)

def sequential(params, x):
    ws, bs = params
    h = x.reshape(M * MB, D)
    for i in range(L):
        h = layer(ws[i], bs[i], h)
    return h.reshape(M, MB, D)

def stage_fn(stage_params, h):
    sw, sb = stage_params
    for i in range(sw.shape[0]):
        h = layer(sw[i], sb[i], h)
    return h

mesh = make_mesh((4,), ("pod",))
staged = split_stages((ws, bs), 4)
with jax.set_mesh(mesh):
    out_pipe = pipeline_apply(stage_fn, staged, x, mesh)
out_ref = sequential((ws, bs), x)
err = float(jnp.max(jnp.abs(out_pipe - out_ref)))
assert err < 1e-5, f"forward mismatch {err}"

# gradient equivalence: grad wrt weights through the pipeline
def loss_pipe(params):
    # pipeline_apply's shard_map names its mesh; set_mesh cannot be
    # entered under the grad trace
    staged = split_stages(params, 4)
    return jnp.sum(pipeline_apply(stage_fn, staged, x, mesh) ** 2)

def loss_ref(params):
    return jnp.sum(sequential(params, x) ** 2)

g_pipe = jax.grad(loss_pipe)((ws, bs))
g_ref = jax.grad(loss_ref)((ws, bs))
for a, b in zip(jax.tree_util.tree_leaves(g_pipe), jax.tree_util.tree_leaves(g_ref)):
    gerr = float(jnp.max(jnp.abs(a - b)))
    assert gerr < 1e-4, f"grad mismatch {gerr}"
print("PIPELINE_OK")
"""


def test_pipeline_forward_and_grad_equivalence():
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
                         text=True, timeout=600, env=env)
    assert "PIPELINE_OK" in res.stdout, res.stdout + res.stderr
