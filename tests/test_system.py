"""End-to-end system behaviour: the paper's technique wired through the
full stack (quantized serving with DLA energy accounting, uGEMM accuracy
claim, workload pricing against the paper's findings)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import accounting, gemm_sims as gs
from repro.launch.mesh import single_device_mesh
from repro.models import model as M


class TestQuantizedExecution:
    def test_quant_kernel_inference_close_to_float(self, rng):
        """Running a smoke model through the Pallas int8 path ~ float path."""
        cfg = configs.get_smoke_config("phi3-mini-3.8b").replace(
            compute_dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
        ref_logits, _ = M.forward(params, cfg, toks)
        qcfg = cfg.replace(quant_bits=8, quant_kernel=True,
                           quant_backend="tubgemm")
        q_logits, _ = M.forward(params, qcfg, toks)
        agree = float(jnp.mean((jnp.argmax(ref_logits, -1) ==
                                jnp.argmax(q_logits, -1)).astype(jnp.float32)))
        assert agree > 0.7, f"top-1 agreement {agree}"

    def test_exact_designs_identical_outputs(self, rng):
        """tuGEMM / tubGEMM / bGEMM backends are numerically identical."""
        cfg = configs.get_smoke_config("internlm2-1.8b").replace(
            compute_dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
        outs = {}
        for backend in ("tubgemm", "tugemm", "bgemm"):
            qcfg = cfg.replace(quant_bits=8, quant_kernel=True,
                               quant_backend=backend)
            out, _ = M.forward(params, qcfg, toks)
            outs[backend] = np.asarray(out)
        np.testing.assert_array_equal(outs["tubgemm"], outs["tugemm"])
        np.testing.assert_array_equal(outs["tubgemm"], outs["bgemm"])


class TestUGEMMAccuracyClaim:
    def test_model_level_accuracy_drop(self):
        """Paper §V: quantized-model accuracy drops under uGEMM's stochastic
        compute (96.08 -> 94.7 on their MLP) but stays usable; measured here
        as top-1 logits agreement vs the exact INT8 path."""
        # its own generator: the session ``rng``'s state depends on which
        # tests ran before this one in the same worker
        rng = np.random.default_rng(0)
        cfg = configs.get_smoke_config("internlm2-1.8b").replace(
            compute_dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
        ref, _ = M.forward(params, cfg.replace(quant_bits=8, quant_kernel=True,
                                               quant_backend="bgemm"), toks)
        uout, _ = M.forward(params, cfg.replace(quant_bits=8, quant_kernel=True,
                                                quant_backend="ugemm"), toks)
        agree = float(jnp.mean((jnp.argmax(ref, -1) ==
                                jnp.argmax(uout, -1)).astype(jnp.float32)))
        assert 0.5 < agree <= 1.0


class TestEndToEndEnergyAccounting:
    def test_serving_cost_report(self, rng):
        """Full-model DLA pricing reproduces the paper's ordering."""
        from repro.launch.serve import build_workload
        cfg = configs.get_smoke_config("llama3-8b")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        rec, stats = build_workload(cfg, params, batch=4, ctx_len=16, bits=4)
        assert rec.calls and all(0 <= c.bit_sparsity <= 1 for c in rec.calls)
        costs = {d: accounting.price_workload(rec.calls, design=d, bits=4,
                                              unit_n=128, num_units=16)
                 for d in gs.DESIGNS}
        # Table IV at 128x128/4-bit: tubGEMM beats bGEMM on energy;
        # tuGEMM pays enormous latency; only temporal designs see Eq.1 savings
        assert costs["tubgemm"].wc_energy_uj < costs["bgemm"].wc_energy_uj
        assert costs["tugemm"].dyn_latency_us > \
            10 * costs["tubgemm"].dyn_latency_us
        assert costs["tubgemm"].sparsity_saving >= 0
        assert costs["bgemm"].sparsity_saving == pytest.approx(0.0)

    def test_generate_runs(self, rng):
        from repro.launch.serve import generate
        cfg = configs.get_smoke_config("internlm2-1.8b")
        mesh = single_device_mesh()
        with jax.set_mesh(mesh):
            params = M.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
        toks = generate(cfg, params, mesh, prompt, max_new=6)
        assert toks.shape == (2, 6)
        assert int(jnp.max(toks)) < cfg.vocab_size


class TestBackendExecution:
    """serve --execute-backend: the model actually runs on the typed backend."""

    def test_serve_execute_backend_end_to_end(self, rng):
        from repro import backends
        from repro.launch import serve
        cfg = configs.get_smoke_config("llama3-8b")
        mesh = single_device_mesh()
        with jax.set_mesh(mesh):
            params = M.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
        backend = backends.resolve("tubgemm", bits=4)
        result = serve.run_backend_execution(
            cfg, params, mesh, prompt, backend, 4, unit_n=128, num_units=64)
        assert result["tokens"].shape == (2, 4)
        assert int(jnp.max(result["tokens"])) < cfg.vocab_size
        assert result["sites"] > 0                    # dense layers contracted
        assert result["rel_rmse"] == 0.0              # int GEMMs == oracle
        assert 0.0 <= result["top1_agreement"] <= 1.0
        cyc = result["cycles"]
        assert cyc["dyn_floor"] - 0.5 <= cyc["measured"] <= cyc["wc"] + 0.5
        # nothing leaked: later code sees the float path again
        assert backends.active_backend() is None


class TestPaperSweepConfig:
    def test_grids(self):
        from repro.configs import paper_gemm
        grid = paper_gemm.table_grid()
        assert len(grid) == 3 * 2 * 4       # bits x sizes x designs
        tpu = paper_gemm.tpu_grid()
        assert {c.n for c in tpu} == {64, 128}
        assert all(c.bits == 4 for c in tpu)
