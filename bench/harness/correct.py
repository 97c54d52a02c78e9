"""The comparison that decides ``correct``.

Once the window has closed and the engine is gone, a sample of the
finished requests, drawn from the seed and always holding the one with the
most served tokens, is run through the plain float32 reference (the
forward pass of the cell's architecture, ``archs/<arch>.py``) once per
request: its prompt followed by the tokens the engine served.  At each
served position the reference's logits say how far the served token lies
below the reference's best token.  The widest such
gap is compared with the cell's limit; greedy decoding that computes the
model at its stated precision picks a token within rounding of the best.

The control puts the reference, with its weights rounded to float8, in the
program's place: at each of the same positions it picks its own best
token, and the float32 reference reads that token's gap the same way.
"""

from __future__ import annotations

import numpy as np

from harness import reference, traffic

#: served tokens a sample holds at least (with two requests at least)
SAMPLE_TOKENS = 300


def sample(served, seed: int, min_tokens: int = SAMPLE_TOKENS) -> list[int]:
    """Finished requests to compare: the longest, then others by the seed."""
    done = sorted(served.finished)
    if not done:
        return []
    n_tok = {r: len(served.tokens[r]) for r in done}
    longest = max(done, key=lambda r: (n_tok[r], -r))
    rest = [r for r in done if r != longest]
    order = np.random.default_rng([int(seed), 2]).permutation(len(rest))
    picked, total = [longest], n_tok[longest]
    for i in order:
        if total >= min_tokens and len(picked) >= 2:
            break
        picked.append(rest[i])
        total += n_tok[rest[i]]
    return picked


def readings(cell, params, seed: int, served, *, control: bool = False,
             picked: list[int] | None = None) -> dict:
    """The widest gap of the served tokens (and of the control's picks)."""
    d = cell.dims
    prompt_len = {a.req_id: a.prompt_len for a in served.arrivals}
    picked = sample(served, seed) if picked is None else picked
    worst, worst_control, rows_total = 0.0, 0.0, 0
    for r in picked:
        prompt = traffic.prompt_tokens(seed, r, prompt_len[r], d.vocab)
        out = np.asarray(served.tokens[r], np.int32)
        seq = np.zeros(cell.max_seq_len, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(out) - 1] = out[:-1]
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        chosen = np.zeros(cell.max_seq_len, np.int32)
        chosen[rows] = out
        ref = cell.arch.logits(params, seq, d=d)
        gaps = np.asarray(reference.gaps(ref, chosen))[rows]
        worst = max(worst, float(gaps.max()))
        rows_total += len(rows)
        if control:
            pick = reference.first_choice(cell.arch.logits(params, seq, d=d,
                                                           fp8=True))
            cgaps = np.asarray(reference.gaps(ref, pick))[rows]
            worst_control = max(worst_control, float(cgaps.max()))
        del ref
    out = {"max_logit_gap": worst, "requests": len(picked),
           "tokens": rows_total}
    if control:
        out["control_gap"] = worst_control
    return out


def compare(cell, params, seed: int, served) -> dict:
    """``correct`` and each number compared beside its limit."""
    unfinished = len(served.arrivals) - len(served.finished)
    read = readings(cell, params, seed, served)
    checks = {
        "unfinished": {"value": unfinished, "limit": 0},
        "max_logit_gap": {"value": read["max_logit_gap"],
                          "limit": cell.limits["max_logit_gap"]},
    }
    ok = (unfinished == 0 and read["requests"] > 0
          and read["max_logit_gap"] <= cell.limits["max_logit_gap"])
    return {"correct": ok, "checks": checks, "sampled": read}
