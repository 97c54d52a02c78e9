"""Distribution layer on the single real CPU device: steps build/run under a
trivial mesh, sharding trees are well-formed, HLO cost analysis is exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro import configs
from repro.launch import hlo_cost, steps as steps_lib
from repro.launch.mesh import single_device_mesh
from repro.models import model as M
from repro.optim import AdamWConfig


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


class TestSteps:
    def test_train_step_runs_and_descends(self, mesh, rng):
        cfg = configs.get_smoke_config("internlm2-1.8b")
        opt_cfg = AdamWConfig(lr=1e-3)
        with jax.set_mesh(mesh):
            step = steps_lib.make_train_step(cfg, mesh, opt_cfg, donate=False)
            state = steps_lib.init_train_state(cfg, opt_cfg, jax.random.PRNGKey(0))
            batch = {
                "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                                      jnp.int32),
                "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)),
                                       jnp.int32)}
            losses = []
            for _ in range(5):
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
            assert losses[-1] < losses[0]
            assert int(state.step) == 5

    def test_prefill_decode_steps(self, mesh, rng):
        cfg = configs.get_smoke_config("llama3-8b")
        with jax.set_mesh(mesh):
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            pstep = steps_lib.make_prefill_step(cfg, mesh)
            dstep = steps_lib.make_decode_step(cfg, mesh)
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
            caches = M.init_caches(cfg, 2, 16, dtype=jnp.bfloat16)
            logits, caches = pstep(params, {"tokens": toks}, caches)
            assert logits.shape == (2, 8, cfg.vocab_size)
            dlog, caches = dstep(params, toks[:, -1:], caches, jnp.int32(8))
            assert dlog.shape == (2, 1, cfg.vocab_size)
            assert not bool(jnp.any(jnp.isnan(dlog)))

    def test_input_specs_cover_all_cells(self):
        for arch, shape in configs.cells():
            cfg = configs.get_config(arch)
            ins = steps_lib.input_specs(cfg, shape)
            sh = configs.SHAPES[shape]
            if sh["step"] == "decode":
                assert ins["tokens"].shape == (sh["global_batch"], 1)
            else:
                key = "embeds" if cfg.frontend_stub else "tokens"
                assert ins[key].shape[:2] == (sh["global_batch"], sh["seq_len"])

    def test_pspec_trees_match_param_trees(self, mesh):
        for arch in ("llama3-8b", "deepseek-v3-671b", "zamba2-1.2b", "rwkv6-3b"):
            cfg = configs.get_smoke_config(arch)
            params = jax.eval_shape(
                lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
            specs = M.param_pspecs(cfg, mesh)
            jax.tree_util.tree_map(lambda a, b: None, params, specs)  # same treedef


class TestHloCost:
    def test_scan_vs_unroll_flops_identical(self):
        def f_scan(x, w):
            return lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None,
                            length=24)[0]

        def f_unroll(x, w):
            c = x
            for _ in range(24):
                c = jnp.tanh(c @ w)
            return c

        x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        expected = 2 * 8 * 128 * 128 * 24
        for f in (f_scan, f_unroll):
            c = hlo_cost.analyze(jax.jit(f).lower(x, w).compile().as_text())
            assert c.flops == expected

    def test_nested_scan(self):
        def g(x, w):
            def outer(c, _):
                inner = lax.scan(lambda ci, _: (ci @ w, None), c, None, length=4)
                return inner[0], None
            return lax.scan(outer, x, None, length=6)[0]

        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c = hlo_cost.analyze(jax.jit(g).lower(x, w).compile().as_text())
        assert c.flops == 2 * 8 * 64 * 64 * 24

    def test_bytes_amortize_loop_invariant_buffers(self):
        """A scan slicing a stacked weight must charge ~the stack once, not
        stack x trips."""
        L, D = 16, 256

        def f(x, ws):
            return lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

        x = jax.ShapeDtypeStruct((4, D), jnp.float32)
        ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
        c = hlo_cost.analyze(jax.jit(f).lower(x, ws).compile().as_text())
        stack_bytes = L * D * D * 4
        assert c.bytes_accessed < 6 * stack_bytes  # would be ~L x with the bug

    def test_roofline_terms(self):
        from repro.launch.hlo_stats import CollectiveStats, roofline
        coll = CollectiveStats(total_bytes=1e9, by_op={}, counts={})
        t = roofline({"flops": 197e12, "bytes accessed": 819e9}, coll,
                     chips=256, model_flops=197e12 * 256 * 0.5)
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(1.0)
        assert t.collective_s == pytest.approx(1e9 / 50e9)
        assert t.dominant in ("compute", "memory")
        assert t.useful_flops_ratio == pytest.approx(0.5)


class TestGradCompression:
    def test_error_feedback_reduces_bias(self, rng):
        from repro.optim.compression import compress_with_error_feedback
        g = {"w": jnp.asarray(rng.normal(0, 1e-3, (64, 64)), jnp.float32)}
        ef = {"w": jnp.zeros((64, 64), jnp.float32)}
        total = jnp.zeros((64, 64), jnp.float32)
        for _ in range(8):
            out, ef = compress_with_error_feedback(g, ef)
            total = total + out["w"]
        # accumulated compressed grads ~ accumulated true grads; the residual
        # is bounded by ONE quantization step (amax/127), not zero
        step = float(jnp.max(jnp.abs(g["w"]))) / 127.0
        np.testing.assert_allclose(np.asarray(total), np.asarray(8 * g["w"]),
                                   rtol=0.05, atol=2 * step)

    def test_int8_psum_single_device(self, mesh, rng):
        from repro.optim.compression import int8_psum
        g = {"w": jnp.asarray(rng.normal(0, 1, (32, 32)), jnp.float32)}
        with jax.set_mesh(mesh):
            out = int8_psum(g, mesh, axis="data")
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                                   rtol=0.02, atol=0.02)

    def test_compressed_train_step(self, mesh, rng):
        cfg = configs.get_smoke_config("phi3-mini-3.8b")
        opt_cfg = AdamWConfig(lr=1e-3, compress_grads=True)
        with jax.set_mesh(mesh):
            step = steps_lib.make_train_step(cfg, mesh, opt_cfg, donate=False)
            state = steps_lib.init_train_state(cfg, opt_cfg, jax.random.PRNGKey(0))
            batch = {
                "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                                      jnp.int32),
                "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)),
                                       jnp.int32)}
            l0 = None
            for _ in range(5):
                state, metrics = step(state, batch)
                l0 = float(metrics["loss"]) if l0 is None else l0
            assert float(metrics["loss"]) < l0
