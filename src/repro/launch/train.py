"""End-to-end training driver with fault tolerance.

Runs at any scale: the examples train a ~few-M-param smoke config on this
CPU container; on TPU the same loop drives the production mesh.  Features:
auto-resume from the latest COMPLETE checkpoint, keep-k async checkpointing,
straggler watchdog, per-step retry, and optional int8 gradient compression.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/run1
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, make_pipeline
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_mesh, make_production_mesh, single_device_mesh
from repro.optim import AdamWConfig, cosine_schedule
from repro.runtime import StepTimer, StragglerWatchdog, retry_with_backoff

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainLoopConfig:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    keep: int = 3
    seed: int = 0
    batch: int = 8
    seq: int = 128
    lr: float = 3e-4
    warmup: int = 20
    compress_grads: bool = False
    inject_failures: float = 0.0    # probability of a synthetic step failure


def train(cfg, mesh, loop: TrainLoopConfig):
    opt_cfg = AdamWConfig(lr=loop.lr, compress_grads=loop.compress_grads)
    sched = cosine_schedule(loop.lr, loop.warmup, loop.steps)
    step_fn = steps_lib.make_train_step(cfg, mesh, opt_cfg, sched)

    data_cfg = DataConfig(batch_size=loop.batch, seq_len=loop.seq + 1,
                          vocab_size=cfg.vocab_size, seed=loop.seed,
                          embed_dim=cfg.d_model if cfg.frontend_stub else None)
    data = make_pipeline(data_cfg)

    mgr = CheckpointManager(loop.ckpt_dir, keep=loop.keep) if loop.ckpt_dir else None
    with jax.set_mesh(mesh):
        state = steps_lib.init_train_state(cfg, opt_cfg,
                                           jax.random.PRNGKey(loop.seed))
        start = 0
        if mgr is not None and mgr.has_checkpoint():
            st_specs = steps_lib.named(mesh, steps_lib.train_state_pspecs(cfg, mesh))
            state, start, extras = mgr.restore_latest(state, shardings=st_specs)
            log.info("auto-resumed from step %d", start)

        watchdog = StragglerWatchdog()
        rng = np.random.default_rng(loop.seed + 1)
        history = []
        for i in range(start, loop.steps):
            batch_np = next(data)
            batch = {k: jnp.asarray(v) for k, v in batch_np.items()
                     if k in ("tokens", "targets", "embeds")}
            if cfg.frontend_stub:
                batch.pop("tokens", None)

            def do_step():
                if loop.inject_failures and rng.random() < loop.inject_failures:
                    raise RuntimeError("synthetic node failure (injected)")
                return step_fn(state, batch)

            with StepTimer(watchdog):
                state, metrics = retry_with_backoff(do_step, retries=3,
                                                    base_delay=0.01)
            if (i + 1) % loop.log_every == 0 or i == start:
                m = {k: float(v) for k, v in metrics.items()}
                history.append((i + 1, m))
                log.info("step %d loss=%.4f nll=%.4f gnorm=%.2f lr=%.2e",
                         i + 1, m["loss"], m["nll"], m["grad_norm"], m["lr"])
            if mgr is not None and (i + 1) % loop.ckpt_every == 0:
                mgr.save(i + 1, state, extras={"loss": float(metrics["loss"])})
        if mgr is not None:
            mgr.save(loop.steps, state)
            mgr.wait()
    return state, history, watchdog


def main() -> int:
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--inject-failures", type=float, default=0.0)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "pod", "multipod"])
    args = ap.parse_args()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if args.mesh == "single":
        mesh = single_device_mesh()
    elif args.mesh == "pod":
        mesh = make_production_mesh()
    else:
        mesh = make_production_mesh(multi_pod=True)

    loop = TrainLoopConfig(steps=args.steps, batch=args.batch, seq=args.seq,
                           lr=args.lr, ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every,
                           compress_grads=args.compress_grads,
                           inject_failures=args.inject_failures)
    t0 = time.time()
    state, history, watchdog = train(cfg, mesh, loop)
    if history:
        first, last = history[0][1]["loss"], history[-1][1]["loss"]
        print(f"trained {args.arch} ({'smoke' if args.smoke else 'full'}): "
              f"loss {first:.4f} -> {last:.4f} in {time.time()-t0:.1f}s "
              f"({watchdog.slow_steps} straggler steps)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
