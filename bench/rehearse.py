"""Compile each cell's decode step and largest prefill for a described v5e.

    JAX_PLATFORMS=cpu python bench/rehearse.py [workload ...]

No chip is needed: the TPU compiler compiles for a chip that is described
and not attached, at the cell's own sizes, and prints what
``memory_analysis()`` counts for each program.  Nothing runs, so this says
nothing about results or times; it refuses what the chip's compiler would
refuse and shows what one program needs besides what the process keeps on
the device (weights, pools).
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _abstract(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _engine(cell, params, mesh):
    """An engine object with its attributes set by hand: the constructor
    walks real weights, and here there are none."""
    from repro import backends
    from repro.serving.engine import ServingEngine
    e = object.__new__(ServingEngine)
    mode = cell.mode
    e.cfg, e.params = cell.cfg, params
    e.max_batch, e.page_size, e.max_seq_len = (cell.max_batch, cell.page_size,
                                               cell.max_seq_len)
    e.num_pages = 1 + cell.max_batch * -(-cell.max_seq_len // cell.page_size)
    e.backend = mode.get("backend")
    e.bits = mode.get("bits", 4)
    e.plan, e.grid, e.packed = None, None, mode.get("packed", False)
    e.attention, e.attention_impl, e._fused_interpret = "fused", "pallas", False
    e.batched_prefill, e._mesh = True, mesh
    e._exec_params = params
    if e.packed:
        import jax
        e._exec_params = jax.eval_shape(
            lambda p: backends.pack_weights(cell.cfg, p, bits=e.bits), params)
    return e


def _report(tag: str, compiled) -> None:
    m = compiled.memory_analysis()
    gb = 1e9
    print(f"{tag}: arguments {m.argument_size_in_bytes / gb:.3f} GB, outputs "
          f"{m.output_size_in_bytes / gb:.3f} GB, temporaries "
          f"{m.temp_size_in_bytes / gb:.3f} GB, aliased "
          f"{m.alias_size_in_bytes / gb:.3f} GB, code "
          f"{m.generated_code_size_in_bytes / 1e6:.1f} MB; Mosaic kernel: "
          f"{'tpu_custom_call' in compiled.as_text()}", flush=True)


def rehearse(name: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding

    from harness import serve, spec, weights
    from repro.serving import engine as engine_lib

    cell = spec.load_cell(name)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    params = _abstract(jax.eval_shape(
        lambda: weights.make(cell.arch.shapes(cell.dims), 0)), one)
    e = _engine(cell, params, mesh)
    e._exec_params = _abstract(e._exec_params, one)
    b, cfg = cell.max_batch, cell.cfg
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, e.num_pages, cell.page_size, cfg.num_kv_heads,
         cfg.resolved_head_dim), jnp.float32, sharding=one)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    blocks = -(-cell.max_seq_len // cell.page_size)
    pool_gb = 2 * np.prod(pool.shape) * 4 / 1e9
    weights_gb = sum(np.prod(x.shape) * x.dtype.itemsize for x in
                     jax.tree_util.tree_leaves(e._exec_params)) / 1e9
    print(f"{name}: weights as executed {weights_gb:.3f} GB, K+V pools "
          f"{pool_gb:.3f} GB ({e.num_pages} pages)", flush=True)
    widest = max(engine_lib._bucket(n) for n in range(
        cell.traffic["prompt"]["min"], cell.traffic["prompt"]["max"] + 1))
    jax.config.update("jax_enable_compilation_cache", False)
    with jax.set_mesh(mesh), e._scope(), serve.mode_scope(cell):
        decode = jax.jit(e._decode_fn).lower(
            e._exec_params, arr((b, 1), jnp.int32), pool, pool,
            arr((b, blocks), jnp.int32), arr((b,), jnp.int32),
            arr((b,), jnp.bool_)).compile()
        _report(f"{name}: decode ({b} slots)", decode)
        got = {}

        def capture(key, make):
            got["fn"] = make()
            return lambda *a: got["fn"].lower(*a).compile()

        orig = engine_lib._prefill_cache_get
        engine_lib._prefill_cache_get = capture
        try:
            prefill = e._prefill(arr((b, widest), jnp.int32))
        finally:
            engine_lib._prefill_cache_get = orig
        _report(f"{name}: prefill ({b}, {widest})", prefill)


def main(argv=None) -> int:
    from harness import spec
    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in spec.load_benchmark()["workloads"]]
    for name in names:
        rehearse(name)
    return 0


if __name__ == "__main__":
    sys.exit(main())
