#!/usr/bin/env python3
"""Bring-up smoke test: serve internlm2-1.8b at its published widths on a TPU.

    python3 chip_smoke.py              # one chip: build, float, backend, kernel
    python3 chip_smoke.py --four-chips # 2x2 PE-grid engine vs one chip only

Drives the paged continuous-batching engine (``repro.serving.ServingEngine``)
through ``ServingEngine.run`` at internlm2-1.8b's published widths (24
layers, d_model 2048, 16 heads / 8 KV heads, d_ff 8192, vocab 92544) with
seeded random weights, in one process that owns the chip:

* **build** — params from ``--seed``;
* **float** — a seeded trace of 6 requests (prompts 128-512 tokens, 16-64
  output tokens) served twice (cold = compile + serve, warm = serve); the
  decode logits are then held against the float32 reference forward pass
  (``repro.serving.logit_check``);
* **backend** — the same trace on tubGEMM@4 from the bit-packed store with
  per-row activation scales: the continuous and static schedulers must
  emit identical token streams; a gather-oracle replay is reported;
* **kernel** — one full-width GEMM site (8 x 2048 @ 2048 x 8192) through the
  ``tubgemm_pallas`` kernel, bit-exact against ``gemm_sims.bgemm_exact``;
* **four chips** (``--four-chips``, in place of the phases above) — the same
  site on a 2x2 tubGEMM grid, bit-exact; the backend trace served on a 2x2
  PE-grid engine beside the one-chip engine; and the float trace served on
  a 2x2 grid engine, its decode logits held against the float32 reference.

Exits non-zero, without the final JSON line, when JAX finds no TPU or any
phase fails.  The last line of stdout on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "internlm2-1.8b"
MAX_BATCH = 4
PAGE_SIZE = 8
MAX_SEQ_LEN = 1024


def log(msg: str) -> None:
    print(msg, flush=True)


def traffic(seed: int):
    from repro.serving import TrafficConfig, generate_trace
    return generate_trace(TrafficConfig(
        num_requests=6, arrival_rate=1.0, prompt_short=(128, 256),
        prompt_long=(384, 512), output_short=(16, 32), output_long=(48, 64),
        p_long=0.5, seed=seed))


def decode_lowering(engine) -> str:
    """Which decode-attention lowering the engine's jitted step contains."""
    import jax
    import jax.numpy as jnp
    cfg, b = engine.cfg, engine.max_batch
    blocks = -(-engine.max_seq_len // engine.page_size)
    pool = jax.ShapeDtypeStruct(
        (cfg.num_layers, engine.num_pages, engine.page_size,
         cfg.num_kv_heads, cfg.resolved_head_dim), jnp.float32)
    with jax.set_mesh(engine._mesh), engine._scope():
        text = engine._decode.lower(
            engine._exec_params, jax.ShapeDtypeStruct((b, 1), jnp.int32),
            pool, pool, jax.ShapeDtypeStruct((b, blocks), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.int32),
            jax.ShapeDtypeStruct((b,), jnp.bool_)).as_text()
    if "tpu_custom_call" in text:
        return "pallas (Mosaic kernel compiled into the decode step)"
    if engine._fused_interpret:
        return "pallas-interpret"
    return "xla"


def served(engine, trace, scheduler="continuous"):
    t0 = time.perf_counter()
    report = engine.run(trace, scheduler)
    return report, time.perf_counter() - t0


def float_phase(cfg, params, trace) -> tuple[bool, int]:
    from repro.serving import ServingEngine
    from repro.serving.logit_check import check_decode_logits
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, params, max_batch=MAX_BATCH,
                           page_size=PAGE_SIZE, max_seq_len=MAX_SEQ_LEN)
    log(f"[float] engine built (energy-model weight walk) in "
        f"{time.perf_counter() - t0:.2f} s")
    lowering = decode_lowering(engine)
    log(f"[float] decode attention lowering: {lowering}")
    cold, t_cold = served(engine, trace)
    warm, t_warm = served(engine, trace)
    log(f"[float] served {cold.requests}/{len(trace)} requests, "
        f"{cold.tokens} tokens in {cold.steps} steps: cold run {t_cold:.2f} s "
        f"(compile + serve), warm run {t_warm:.2f} s (serve); compile "
        f"~{t_cold - t_warm:.2f} s")
    same = cold.request_tokens == warm.request_tokens
    log(f"[float] warm run repeats the cold run's token streams: {same}")
    t0 = time.perf_counter()
    check = check_decode_logits(
        engine, [engine.prompt_tokens(r) for r in trace],
        [cold.request_tokens[r.req_id] for r in trace])
    log(f"[float] {check.line()} ({time.perf_counter() - t0:.2f} s)")
    # the replay runs the engine's own programs (prefill at its one fixed
    # batch, row-wise decode), so it reproduces every served token by
    # construction
    ok = (lowering.startswith("pallas (") and cold.requests == len(trace)
          and warm.requests == len(trace) and same and check.ok
          and check.replay_agreement == 1.0)
    return ok, cold.tokens + warm.tokens


def first_divergence(a: dict, b: dict) -> dict:
    """req_id -> index of the first token where two streams differ."""
    return {r: next((i for i, (x, y) in enumerate(zip(t, b[r])) if x != y),
                    min(len(t), len(b[r])))
            for r, t in a.items() if t != b[r]}


def backend_phase(cfg, params, trace, grid=None, static=True):
    """tubGEMM@4, packed store, per-row scales.

    Strict: every request completes and, with ``static``, the continuous
    and static schedulers emit identical streams (every prefill call has
    one fixed shape and decode is row-wise, so a request's codes never see
    its neighbours).  Informational: a replay on the gather attention
    oracle, which rounds K, V and the softmax weights at other points than
    the Pallas kernel; 4-bit codes turn that into different tokens.
    Returns (ok, continuous streams, tokens).
    """
    from repro.models.common import activation_scaling
    from repro.serving import ServingEngine
    kw = dict(max_batch=MAX_BATCH, page_size=PAGE_SIZE,
              max_seq_len=MAX_SEQ_LEN, backend="tubgemm", bits=4,
              packed=True, grid=grid)
    tag = f"[backend{' grid %dx%d' % grid if grid else ''}]"
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, params, **kw)
    log(f"{tag} engine built (weight walk + packing) in "
        f"{time.perf_counter() - t0:.2f} s")
    with activation_scaling("per-row"):
        cont, t_cont = served(engine, trace, "continuous")
        log(f"{tag} tubgemm@4 packed, per-row act scales: continuous "
            f"{cont.requests}/{len(trace)} requests {cont.tokens} tokens "
            f"{t_cont:.2f} s; {cont.energy_per_token_uj:.4f} uJ/token "
            f"(Eq. 1)")
        ok = cont.requests == len(trace)
        tokens = cont.tokens
        if grid:
            device_memory(tag)
        if static:
            stat, t_stat = served(engine, trace, "static")
            del engine
            gather = ServingEngine(cfg, params, attention="gather", **kw)
            gath, t_gath = served(gather, trace, "continuous")
            del gather
            same = cont.request_tokens == stat.request_tokens
            log(f"{tag} static {stat.requests}/{len(trace)} requests "
                f"{t_stat:.2f} s: token streams identical to continuous: "
                f"{same}")
            log(f"{tag} gather-oracle replay {gath.requests}/{len(trace)} "
                f"requests {t_gath:.2f} s (informational): first differing "
                f"token per request "
                f"{first_divergence(cont.request_tokens, gath.request_tokens)}")
            ok = ok and same and stat.requests == gath.requests == len(trace)
            tokens += stat.tokens + gath.tokens
    return ok, cont.request_tokens, tokens


def gemm_site(seed: int, backend, tag: str) -> bool:
    """One full-width site (8 x 2048 @ 2048 x 8192, 4-bit codes) through
    ``backend``, bit-exact against the binary oracle."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import gemm_sims
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(-7, 8, (8, 2048)), jnp.int8)
    b = jnp.asarray(rng.integers(-7, 8, (2048, 8192)), jnp.int8)
    t0 = time.perf_counter()
    got = np.asarray(backend.execute(a, b))
    dt = time.perf_counter() - t0
    exact = bool(np.array_equal(got, np.asarray(gemm_sims.bgemm_exact(a, b))))
    log(f"{tag} 8x2048 @ 2048x8192: bit-exact vs bgemm_exact: {exact} "
        f"({dt:.2f} s incl. compile)")
    return exact


def kernel_phase(seed: int) -> bool:
    from repro import backends
    # compiled Mosaic kernel, never the interpreter
    backend = backends.resolve("tubgemm_pallas", bits=4, interpret=False)
    return gemm_site(seed, backend, "[kernel] tubgemm_pallas (compiled)")


def device_memory(tag: str) -> None:
    import jax
    for d in jax.devices():
        stats = d.memory_stats() or {}
        log(f"{tag} {d}: bytes_in_use {stats.get('bytes_in_use', 'n/a')}, "
            f"peak {stats.get('peak_bytes_in_use', 'n/a')}")


def four_chip_phase(cfg, params, trace, seed: int) -> bool:
    """The 2x2 PE grid against one chip.

    Strict: a full-width site bit-exact on the grid (int32 partial sums
    psum-reduced); the tubGEMM@4 trace served to completion by the grid
    engine and the one-chip engine; and the float model served by a grid
    engine whose decode logits hold against the float32 reference like the
    one-chip float phase's.  Informational: grid vs one-chip tubGEMM@4
    streams — the grid engine is another XLA program around the same
    integer GEMMs, and 4-bit codes turn its float rounding into different
    tokens.  Per-device memory shows where the grid engines' weights and
    pools live.
    """
    from repro import backends
    from repro.serving import ServingEngine
    from repro.serving.logit_check import check_decode_logits
    grid_backend = backends.as_grid(backends.resolve("tubgemm", bits=4), 2, 2)
    exact = gemm_site(seed, grid_backend, "[four-chips] tubgemm 2x2 grid")
    ok1, one_chip, _ = backend_phase(cfg, params, trace, static=False)
    ok4, grid, _ = backend_phase(cfg, params, trace, grid=(2, 2),
                                 static=False)
    log(f"[four-chips] 2x2 grid token streams identical to one chip "
        f"(informational): {one_chip == grid}; first differing token per "
        f"request {first_divergence(one_chip, grid)}")
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, params, max_batch=MAX_BATCH,
                           page_size=PAGE_SIZE, max_seq_len=MAX_SEQ_LEN,
                           grid=(2, 2))
    report = engine.run(trace)
    log(f"[four-chips float grid 2x2] served {report.requests}/{len(trace)} "
        f"requests, {report.tokens} tokens ({time.perf_counter() - t0:.2f} s "
        f"incl. build and compile)")
    device_memory("[four-chips float grid 2x2]")
    check = check_decode_logits(
        engine, [engine.prompt_tokens(r) for r in trace],
        [report.request_tokens[r.req_id] for r in trace])
    log(f"[four-chips float grid 2x2] {check.line()}")
    return (exact and ok1 and ok4 and report.requests == len(trace)
            and check.ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, the trace and the kernel "
                         "operands")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 PE-grid backend phase against "
                         "the one-chip engine (needs 4 chips)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"error: need {need} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compilation cache {cache_dir}")

    from repro import configs
    from repro.models import model as model_lib
    cfg = configs.get_config(ARCH)
    t0 = time.perf_counter()
    # one jitted program instead of an eager dispatch per leaf
    params = jax.jit(model_lib.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    log(f"[build] {ARCH}: {model_lib.count_params(params) / 1e9:.3f} B "
        f"params (fp32) from seed {args.seed} in "
        f"{time.perf_counter() - t0:.2f} s")
    trace = traffic(args.seed)
    log(f"[trace] {len(trace)} requests: prompts "
        f"{[r.prompt_len for r in trace]}, outputs "
        f"{[r.output_len for r in trace]}")

    t_start = time.perf_counter()
    if args.four_chips:
        results = {"four-chips": four_chip_phase(cfg, params, trace,
                                                 args.seed)}
        tokens = None
    else:
        ok_f, tok_f = float_phase(cfg, params, trace)
        ok_b, _, tok_b = backend_phase(cfg, params, trace)
        results = {"float": ok_f, "backend": ok_b,
                   "kernel": kernel_phase(args.seed)}
        tokens = tok_f + tok_b
    stats = dev.memory_stats() or {}
    log(f"phases {results}; {time.perf_counter() - t_start:.2f} s; tokens "
        f"served {tokens}; peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    if not all(results.values()):
        print("error: phase(s) failed: "
              f"{[k for k, v in results.items() if not v]}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
