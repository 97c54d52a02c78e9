"""End-to-end metrics from host timestamps, over the whole window.

Every number here is taken over all the work of the window: a rate is the
tokens that reached the host between the window's opening and its close,
divided by its length; a tail is the percentile of every sample, never a
median of chunks.  A request that never got a token counts as infinitely
late, so a percentile that reaches it is infinite.
"""

from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linear between samples."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    if math.isinf(v[hi]):
        return float(v[lo]) if pos == lo else math.inf
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def output_tok_s(token_at: dict, start: float, seconds: float) -> float:
    """Output tokens on the host inside [start, start + seconds), per second."""
    end = start + seconds
    n = sum(1 for times in token_at.values() for t in times if start <= t < end)
    return n / seconds


def token_gaps(token_at: dict, start: float, seconds: float) -> list[float]:
    """Every gap between consecutive tokens of a request, first to second
    included, whose later token reached the host inside the window."""
    end = start + seconds
    return [b - a for times in token_at.values()
            for a, b in zip(times, times[1:]) if start <= b < end]


def ttfts(due_at: dict, first_at: dict) -> list[float]:
    """Per request due in the window: its due time to its first token."""
    return [first_at[r] - due if r in first_at else math.inf
            for r, due in due_at.items()]


def queue_waits(due_at: dict, picked_at: dict) -> list[float]:
    """Per request due in the window: its due time to its admission."""
    return [picked_at[r] - due if r in picked_at else math.inf
            for r, due in due_at.items()]


def occupancy(decode_steps, max_batch: int) -> float | None:
    """Mean rows decoded per step over the steps that decoded, / max_batch."""
    if not decode_steps:
        return None
    return sum(rows for _, rows, _ in decode_steps) / (
        len(decode_steps) * max_batch)
