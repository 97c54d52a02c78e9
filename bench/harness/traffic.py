"""The one traffic generator: open-loop arrivals in seconds, from a data file.

A mix file (``traffic/<mix>.json``) gives the distributions of prompt and
output lengths; the cell gives the offered rate.  A run of ``seconds`` at
``rate`` offers ``round(rate * seconds)`` requests.  The gaps and lengths
are stratified quantiles of the stated distributions, paired and ordered
once by a fixed stream, so the mix has one sequence of requests.  A seed
only chooses where in that sequence the window opens: the sequence is
rotated, each request keeping the gap that follows it, to start after one
of the longest quarter of its gaps, so that no burst is cut in two.  Every
seed thus offers the same work with the same bursts, in another order.  Gaps
are exponential (a Poisson process conditioned on its count), scaled so
that the last request is due before the window closes.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request: due ``due_s`` seconds after the window opens."""
    req_id: int
    due_s: float
    prompt_len: int
    output_len: int


#: the stream that pairs and orders a mix's requests, the same for every run
ORDER_SEED = 0


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length distribution, in rising order."""
    u = _quantiles(n)
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(int)


def arrivals(mix: dict, rate_per_s: float, seconds: float,
             seed: int) -> list[Arrival]:
    """The requests offered in one window, in due order."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    n = max(1, round(rate_per_s * seconds))
    gaps = -np.log1p(-_quantiles(n)) / rate_per_s
    gaps *= seconds / gaps.sum()
    order = _rng(ORDER_SEED, 0)
    gaps = order.permutation(gaps)
    prompts = order.permutation(lengths(mix["prompt"], n))
    outputs = order.permutation(lengths(mix["output"], n))
    # gaps[i] follows request i: open the window after a lull
    after_lull = np.flatnonzero(np.roll(gaps, 1) >= np.quantile(gaps, 0.75))
    start = int(after_lull[_rng(seed, 0).integers(len(after_lull))])
    gaps, prompts, outputs = (np.roll(x, -start)
                              for x in (gaps, prompts, outputs))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Arrival(i, float(due[i]), int(prompts[i]), int(outputs[i]))
            for i in range(n)]


def prompt_tokens(seed: int, req_id: int, prompt_len: int,
                  vocab: int) -> np.ndarray:
    """The prompt of one request: token ids drawn from the seed."""
    return _rng(seed, 1, req_id).integers(0, vocab, prompt_len).astype(np.int32)


def warmup_prompt_lens(mix: dict, page_size: int, bucket) -> list[int]:
    """Prompt lengths that reach every shape the mix's prompts can reach.

    ``bucket`` maps a prompt length to the program's padded prefill width.
    The lengths cover each width the mix's range can draw and each partial
    last page (``len % page_size``), the shapes the admission path compiles.
    """
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    want_bucket = {bucket(n) for n in range(lo, hi + 1)}
    want_rest = {n % page_size for n in range(lo, hi + 1)}
    picked: list[int] = []
    for n in range(lo, hi + 1):
        b, r = bucket(n), n % page_size
        if b in want_bucket or r in want_rest:
            picked.append(n)
            want_bucket.discard(b)
            want_rest.discard(r)
        if not want_bucket and not want_rest:
            break
    return picked


def warmup_arrivals(mix: dict, page_size: int, bucket,
                    output_len: int = 3) -> list[Arrival]:
    """All due at once: every prefill width, every partial page, decode."""
    return [Arrival(i, 0.0, n, output_len) for i, n in
            enumerate(warmup_prompt_lens(mix, page_size, bucket))]
