"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (the rest of the suite) cannot see what Mosaic refuses:
operand dtypes the MXU does not take, tilings, VMEM budgets.  These tests
hand each kernel ``ShapeDtypeStruct``s placed on a *described* v5e chip
(``jax.experimental.topologies``) and compile it with the TPU compiler that
ships in libtpu — no chip needed.  Each asserts that the Mosaic kernel
(``tpu_custom_call``) is in the compiled program, i.e. that nothing fell
back to an XLA lowering.
The serving engine's admission writer, an XLA program, is held instead to
updating the KV pools in place (``memory_analysis``).

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and every xdist worker imports this
file.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import packed_gemm, unary_gemm
from repro.kernels import paged_attention as paged_lib
from repro.kernels import paged_attention_fused as paf

# internlm2-1.8b decode widths: 4 slots, 16 query / 8 KV heads of 128,
# pages of 8 tokens, 128 blocks (1024 positions) per request
B, H, KVH, HD, PAGE, BLOCKS = 4, 16, 8, 128, 8, 128
M, K, N = 8, 2048, 8192          # one decode-batch GEMM site at d_ff width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("q_dtype", [jnp.bfloat16, jnp.float32])
def test_fused_paged_decode_compiles(one_chip, q_dtype):
    pool = _sds((1 + B * BLOCKS, PAGE, KVH, HD), jnp.float32, one_chip)
    text = _compiled_text(
        lambda q, k, v, bt, ln: paf.fused_paged_decode_attention(
            q, k, v, bt, ln, num_heads=H, impl="pallas"),
        _sds((B, 1, H, HD), q_dtype, one_chip), pool, pool,
        _sds((B, BLOCKS), jnp.int32, one_chip),
        _sds((B,), jnp.int32, one_chip))
    assert "tpu_custom_call" in text


def test_fused_paged_decode_compiles_on_grid_mesh(topo):
    """On a 2x2 PE-grid mesh the page walk runs whole on every chip, inside
    the replicated ``shard_map`` the serving engine wraps it in (XLA cannot
    partition a Mosaic kernel)."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("gx", "gy"),
                axis_types=(AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, P())
    pool = _sds((1 + B * BLOCKS, PAGE, KVH, HD), jnp.float32, rep)
    walk = jax.shard_map(
        functools.partial(paf.fused_paged_decode_attention, num_heads=H,
                          impl="pallas"),
        mesh=mesh, in_specs=(P(),) * 5, out_specs=P(), check_vma=False)
    with jax.set_mesh(mesh):
        text = _compiled_text(
            walk, _sds((B, 1, H, HD), jnp.bfloat16, rep), pool, pool,
            _sds((B, BLOCKS), jnp.int32, rep), _sds((B,), jnp.int32, rep))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("kernel", [unary_gemm.tub_gemm, unary_gemm.tu_gemm],
                         ids=["tub_gemm", "tu_gemm"])
def test_unary_gemm_compiles(one_chip, kernel):
    text = _compiled_text(lambda a, b: kernel(a, b, bits=4)[0],
                          _sds((M, K), jnp.int8, one_chip),
                          _sds((K, N), jnp.int8, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fuse_dequant", [False, True])
def test_packed_gemm_compiles(one_chip, fuse_dequant):
    words = K // 8                                 # 8 codes per word at 4-bit
    text = _compiled_text(
        lambda x, w, s: packed_gemm.packed_gemm(
            x, w, s, bits=4, k=K, fuse_dequant=fuse_dequant),
        _sds((M, K), jnp.int8, one_chip),
        _sds((words, N), jnp.int32, one_chip),
        _sds((1, N), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


def test_prompt_writer_updates_the_pools_in_place(one_chip):
    """The admission writer at internlm2-1.8b's code-cell pool (24 layers,
    577 pages of 16, 8 KV heads of 128) and a 512-wide prefill call of 8
    rows: both donated pools alias its outputs, and its temporaries hold
    less than one pool, so no whole-pool copy is made."""
    layers, pages, page, call_rows, width = 24, 577, 16, 8, 512
    pool = _sds((layers, pages, page, KVH, HD), jnp.float32, one_chip)
    call = _sds((layers, call_rows, width, KVH, HD), jnp.float32, one_chip)
    scalar = _sds((), jnp.int32, one_chip)
    mem = jax.jit(paged_lib.write_prompt_kv, donate_argnums=(0, 1)).lower(
        pool, pool, call, call, scalar, scalar,
        _sds((width // page,), jnp.int32, one_chip)).compile() \
        .memory_analysis()
    pool_bytes = layers * pages * page * KVH * HD * 4
    assert mem.alias_size_in_bytes == 2 * pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes


def test_hybrid_decode_steps_the_slot_states_in_place(one_chip):
    """granite-4.0-h-micro's decode step at its published widths, 16 slots
    of 1792 positions: the attention layers' page walk is the Mosaic
    kernel, and the 36 Mamba2 layers' donated SSM states and conv tails
    alias the step's outputs with no copy of the states made."""
    from jax.sharding import Mesh

    from repro import configs
    from repro.models import model as model_lib
    from repro.serving.engine import ServingEngine

    cfg = configs.get_config("granite-4.0-h-micro").replace(
        param_dtype="bfloat16")
    slots, page, blocks = 16, 16, 112
    # the constructor walks real weights; the step reads only these
    e = object.__new__(ServingEngine)
    e.cfg, e.max_batch, e.page_size = cfg, slots, page
    e.attention, e.attention_impl, e._fused_interpret = "fused", "pallas", False
    e._mesh = Mesh(np.array([one_chip.device_set.pop()]).reshape(1, 1),
                   ("data", "model"))

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = place(jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0))))
    state = place(jax.eval_shape(e._zero_state))
    pool = _sds((4, 1 + slots * blocks, page, 8, 64), jnp.float32, one_chip)
    with jax.set_mesh(e._mesh):
        compiled = jax.jit(e._hybrid_decode_fn, donate_argnums=(7,)).lower(
            params, _sds((slots, 1), jnp.int32, one_chip), pool, pool,
            _sds((slots, blocks), jnp.int32, one_chip),
            _sds((slots,), jnp.int32, one_chip),
            _sds((slots,), jnp.bool_, one_chip), state).compile()
    text = compiled.as_text()
    state_bytes = sum(a.size * 4 for a in jax.tree_util.tree_leaves(state))
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis().alias_size_in_bytes == state_bytes
    shape = "f32[%s]" % ",".join(map(str, state["state"].shape))
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"= {shape}" in line]
