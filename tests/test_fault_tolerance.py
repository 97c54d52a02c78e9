"""Fault tolerance: checkpoint atomicity/retention, auto-resume, elastic
resharding, retries, straggler detection, data pipeline."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, latest_step, restore, save
from repro.data import DataConfig, SyntheticLM, make_pipeline
from repro.runtime import StragglerWatchdog, plan_mesh, retry_with_backoff


class TestCheckpoint:
    def _tree(self, rng):
        return {"a": jnp.asarray(rng.normal(0, 1, (8, 4)), jnp.float32),
                "nested": {"b": jnp.arange(10, dtype=jnp.int32)},
                "scalar": jnp.float32(3.5)}

    def test_roundtrip(self, tmp_path, rng):
        tree = self._tree(rng)
        save(str(tmp_path), 7, tree, extras={"loss": 1.25})
        out, step, extras = restore(str(tmp_path), tree)
        assert step == 7 and extras["loss"] == 1.25
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_incomplete_checkpoint_ignored(self, tmp_path, rng):
        tree = self._tree(rng)
        save(str(tmp_path), 5, tree)
        # simulate a crash mid-save: directory without COMPLETE
        broken = tmp_path / "step_000000009"
        broken.mkdir()
        (broken / "manifest.json").write_text("{}")
        assert latest_step(str(tmp_path)) == 5

    def test_keep_last_k(self, tmp_path, rng):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        tree = self._tree(rng)
        for s in (1, 2, 3, 4):
            mgr.save(s, tree)
        steps = sorted(int(n.split("_")[1]) for n in os.listdir(tmp_path)
                       if n.startswith("step_"))
        assert steps == [3, 4]

    def test_async_save(self, tmp_path, rng):
        mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
        mgr.save(1, self._tree(rng))
        mgr.wait()
        assert latest_step(str(tmp_path)) == 1

    def test_elastic_restore_with_shardings(self, tmp_path, rng):
        """Restore onto explicit (trivial-mesh) shardings — the elastic path."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import single_device_mesh
        tree = self._tree(rng)
        save(str(tmp_path), 3, tree)
        mesh = single_device_mesh()
        shardings = jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, P()), tree)
        out, step, _ = restore(str(tmp_path), tree, shardings=shardings)
        assert step == 3
        assert all(x.sharding == NamedSharding(mesh, P())
                   for x in jax.tree_util.tree_leaves(out))

    def test_shape_mismatch_rejected(self, tmp_path, rng):
        tree = self._tree(rng)
        save(str(tmp_path), 1, tree)
        bad = dict(tree, a=jnp.zeros((4, 4), jnp.float32))
        with pytest.raises(ValueError, match="shape mismatch"):
            restore(str(tmp_path), bad)


class TestRuntime:
    def test_retry_succeeds_after_failures(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("boom")
            return "ok"

        assert retry_with_backoff(flaky, retries=3, base_delay=0.0) == "ok"
        assert calls["n"] == 3

    def test_retry_exhausts(self):
        def dead():
            raise RuntimeError("always")

        with pytest.raises(RuntimeError):
            retry_with_backoff(dead, retries=2, base_delay=0.0)

    def test_straggler_detection(self):
        wd = StragglerWatchdog(threshold=2.0, warmup=3)
        for _ in range(6):
            assert not wd.observe(0.1)
        assert wd.observe(0.5)          # 5x median -> straggler
        assert wd.slow_steps == 1

    def test_plan_mesh_elastic(self):
        # full pods
        assert plan_mesh(512) == ((2, 16, 16), ("pod", "data", "model"))
        assert plan_mesh(256) == ((16, 16), ("data", "model"))
        # degraded: lost 16 chips -> shrink data parallelism
        shape, axes = plan_mesh(240)
        assert shape == (15, 16) and axes == ("data", "model")
        # tiny
        assert plan_mesh(1) == ((1, 1), ("data", "model"))


class TestDataPipeline:
    def test_deterministic_per_host(self):
        cfg = DataConfig(batch_size=2, seq_len=16, vocab_size=64, seed=3)
        a = next(iter(SyntheticLM(cfg)))
        b = next(iter(SyntheticLM(cfg)))
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_hosts_get_disjoint_streams(self):
        cfg0 = DataConfig(batch_size=2, seq_len=16, vocab_size=64, seed=3,
                          host_index=0, host_count=2)
        cfg1 = DataConfig(batch_size=2, seq_len=16, vocab_size=64, seed=3,
                          host_index=1, host_count=2)
        a = next(iter(SyntheticLM(cfg0)))
        b = next(iter(SyntheticLM(cfg1)))
        assert not np.array_equal(a["tokens"], b["tokens"])

    def test_prefetcher(self):
        cfg = DataConfig(batch_size=2, seq_len=8, vocab_size=32)
        it = make_pipeline(cfg, prefetch=2)
        batches = [next(it) for _ in range(5)]
        assert all(b["tokens"].shape == (2, 7) for b in batches)

    def test_token_file(self, tmp_path):
        path = tmp_path / "tokens.bin"
        np.arange(1000, dtype=np.int32).tofile(path)
        cfg = DataConfig(batch_size=2, seq_len=16, path=str(path))
        from repro.data import TokenFile
        b = next(iter(TokenFile(cfg)))
        assert b["tokens"].shape == (2, 16)
        np.testing.assert_array_equal(b["targets"][:, :-1], b["tokens"][:, 1:])

    def test_frontend_stub_embeddings(self):
        cfg = DataConfig(batch_size=2, seq_len=8, vocab_size=32, embed_dim=16)
        b = next(iter(SyntheticLM(cfg)))
        assert b["embeds"].shape == (2, 7, 16)


class TestEndToEndResume:
    def test_train_resume_after_interrupt(self, tmp_path):
        """Loop-level checkpoint/restart: a second run resumes, not restarts."""
        from repro import configs
        from repro.launch.mesh import single_device_mesh
        from repro.launch.train import TrainLoopConfig, train
        cfg = configs.get_smoke_config("musicgen-medium")
        loop = TrainLoopConfig(steps=6, ckpt_every=3, log_every=2,
                               ckpt_dir=str(tmp_path), batch=2, seq=16)
        mesh = single_device_mesh()
        train(cfg, mesh, loop)
        assert latest_step(str(tmp_path)) == 6
        # extend to 8 steps: must resume from 6
        loop2 = TrainLoopConfig(steps=8, ckpt_every=3, log_every=2,
                                ckpt_dir=str(tmp_path), batch=2, seq=16)
        state, history, _ = train(cfg, mesh, loop2)
        assert int(state.step) == 8
        assert history[0][0] >= 6   # first logged step after resume
