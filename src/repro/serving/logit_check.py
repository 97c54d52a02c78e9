"""Serving-path decode logits held against the plain float32 reference.

The check that replaces exact-equality probes wherever exact equality
cannot hold (a TPU computes float32 matmuls in bfloat16 passes, and XLA's
optimizer reassociates reductions).  The *system* side is the engine's own
jitted programs — bucketed prefill, then one ragged decode step per token
through the paged KV cache — teacher-forced with the tokens a served run
emitted.  The *reference* side is :func:`repro.models.reference.
reference_logits`: one causal float32 pass over prompt + emitted tokens at
``Precision.HIGHEST``.  Logit rows are compared, never sampled tokens: with
random weights the top two logits are often within rounding of each other.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.reference import reference_logits
from repro.serving.engine import _bucket
from repro.serving.paged_kv import PagedKVCache

__all__ = ["LogitCheck", "logit_tolerance", "check_decode_logits",
           "BF16_REL_TOL", "F32_REL_TOL"]

#: worst per-row ``||sys - ref||_2 / ||ref||_2`` for a config that computes
#: in bfloat16.  Every matmul operand is rounded to 8 significant bits
#: (unit roundoff 2^-9 ~ 2.0e-3) and a 24-layer residual stream compounds
#: those roundings: measured 1.4-2.5e-2 on internlm2-1.8b's published widths
#: on a v5e and ~1e-2 on the CPU smoke configs.  Rounding the weights of
#: every layer's MLP to float8 (e4m3, 4 significant bits) moves the smoke
#: configs past 8e-2 (tests/test_logit_check.py) and internlm2-1.8b on a
#: v5e past 1e-1, as does float8 in its first layer alone; float8 in its
#: last layer alone stays under 5e-2.
BF16_REL_TOL = 5e-2
#: same for a float32-compute config: only reduction order differs (the
#: fused decode kernel's online softmax, XLA reassociation) on a CPU.
F32_REL_TOL = 1e-4


def logit_tolerance(cfg) -> float:
    """The relative-L2 bound :func:`check_decode_logits` applies to ``cfg``."""
    return F32_REL_TOL if cfg.compute_dtype == "float32" else BF16_REL_TOL


@dataclasses.dataclass(frozen=True)
class LogitCheck:
    """Outcome of one :func:`check_decode_logits` run."""
    rows: int              # logit rows compared (one per emitted token)
    max_rel_l2: float      # worst per-row ||sys - ref|| / ||ref||
    mean_rel_l2: float
    max_abs: float         # worst |sys - ref| over every logit compared
    tol: float
    replay_agreement: float  # argmax(sys) == replayed token, share of rows

    @property
    def ok(self) -> bool:
        return self.max_rel_l2 <= self.tol

    def line(self) -> str:
        return (f"decode logits vs float32 reference ({self.rows} rows): "
                f"max rel-L2 {self.max_rel_l2:.3e} (mean "
                f"{self.mean_rel_l2:.3e}, tol {self.tol:.0e}), max |dlogit| "
                f"{self.max_abs:.3e}; argmax matches the replayed tokens on "
                f"{self.replay_agreement:.1%} of rows: "
                f"{'PASS' if self.ok else 'FAIL'}")


def _system_logits(engine, prompts, outputs) -> list[np.ndarray]:
    """Per request, (len(output), vocab) logits from prefill + paged decode.

    Requests run ``engine.max_batch`` at a time in the engine's own slots
    and through its own admission prefill: row 0 comes off the prefill's
    last position, row ``i`` off the decode step that consumed
    ``output[i - 1]``.
    """
    cfg = engine.cfg
    b = engine.max_batch
    result: list[np.ndarray] = []
    for lo in range(0, len(prompts), b):
        group = list(zip(prompts[lo: lo + b], outputs[lo: lo + b]))
        cache = PagedKVCache(
            num_layers=cfg.num_layers, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, num_pages=engine.num_pages,
            page_size=engine.page_size, max_seq_len=engine.max_seq_len)
        btables = np.zeros((b, cache.max_blocks), np.int32)
        rows: list[list[np.ndarray]] = []
        prefilled = engine._prefill_rows([prompt for prompt, _ in group])
        for slot, ((prompt, out), (last, k_l, v_l)) in enumerate(
                zip(group, prefilled)):
            cache.allocate(slot, len(prompt) + len(out))
            cache.write_prefill(slot, k_l, v_l)
            btables[slot] = cache.block_table_row(slot)
            rows.append([np.asarray(last, np.float32)])
        steps = max(len(out) for _, out in group) - 1
        k_pool, v_pool = cache.k_pool, cache.v_pool
        d_btables = jnp.asarray(btables)
        for i in range(steps):
            tok = np.zeros((b, 1), np.int32)
            pos = np.zeros((b,), np.int32)
            act = np.zeros((b,), bool)
            for slot, (prompt, out) in enumerate(group):
                if i < len(out) - 1:
                    tok[slot, 0] = out[i]
                    pos[slot] = len(prompt) + i
                    act[slot] = True
            lg, k_pool, v_pool, _ = engine._decode(
                engine._exec_params, jnp.asarray(tok), k_pool, v_pool,
                d_btables, jnp.asarray(pos), jnp.asarray(act))
            lg = np.asarray(lg[:, 0], np.float32)
            for slot in np.flatnonzero(act):
                rows[slot].append(lg[slot])
        result.extend(np.stack(r) for r in rows)
    return result


def check_decode_logits(engine, prompts, outputs) -> LogitCheck:
    """Replay served requests through ``engine`` and compare with the
    reference.

    ``prompts`` — per request, its prompt token ids; ``outputs`` — the
    tokens a served run emitted for it (``ServingReport.request_tokens``).
    The engine must run the float model: a backend or plan scope quantizes
    every GEMM, and its drift from the float reference is a different
    question (answered by the bit-exactness gates).
    """
    if engine.backend is not None or engine.plan is not None:
        raise ValueError("check_decode_logits compares the float model; "
                         "build the engine without backend=/plan=")
    prompts = [np.asarray(p, np.int32) for p in prompts]
    outputs = [np.asarray(o, np.int32) for o in outputs]
    if not prompts or len(prompts) != len(outputs) \
            or any(len(o) < 1 for o in outputs):
        raise ValueError("need one non-empty output per prompt")
    with jax.set_mesh(engine._mesh):
        system = _system_logits(engine, prompts, outputs)
    rel, worst_abs, agree, n = [], 0.0, 0, 0
    for prompt, out, sys_rows in zip(prompts, outputs, system):
        seq = np.concatenate([prompt, out[:-1]])
        # causal: tail padding to a power-of-two bucket leaves every real
        # row unchanged and bounds the reference's compiles
        padded = np.zeros(_bucket(len(seq)), np.int32)
        padded[:len(seq)] = seq
        ref = np.asarray(reference_logits(engine.params, engine.cfg, padded)
                         [len(prompt) - 1: len(seq)], np.float32)
        diff = sys_rows - ref
        rel.extend(np.linalg.norm(diff, axis=-1)
                   / np.maximum(np.linalg.norm(ref, axis=-1), 1e-30))
        worst_abs = max(worst_abs, float(np.max(np.abs(diff))))
        agree += int(np.sum(np.argmax(sys_rows, axis=-1) == out))
        n += len(out)
    return LogitCheck(rows=n, max_rel_l2=float(np.max(rel)),
                      mean_rel_l2=float(np.mean(rel)), max_abs=worst_abs,
                      tol=logit_tolerance(engine.cfg),
                      replay_agreement=agree / n)
