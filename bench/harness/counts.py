"""Operations and bytes of the model's work, computed from its shapes.

These are the work the algorithm needs, counted at the configuration's
compute dtype (bfloat16, 2 bytes), whatever the program holds or pads: a
roofline share built on them reads the same work whoever implements it.
"""

from __future__ import annotations

import json
from pathlib import Path

COMPUTE_BYTES = 2  # bfloat16
PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; unknown raises."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def matmul_weights(d) -> int:
    """Weights of every matrix product of one token's forward pass."""
    attn = d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    mlp = 3 * d.d_model * d.d_ff
    return d.layers * (attn + mlp) + d.d_model * d.vocab


def attention_flops(d, ctx_tokens: int) -> int:
    """Scores and weighted values of one query row per attended position,
    summed over ``ctx_tokens`` (query, position) pairs and all layers."""
    return 4 * d.layers * d.heads * d.head_dim * ctx_tokens


def kv_bytes(d, ctx_tokens: int) -> int:
    """K and V of ``ctx_tokens`` cached positions, all layers, at bf16."""
    return 2 * d.layers * d.kv_heads * d.head_dim * COMPUTE_BYTES * ctx_tokens


def decode_step_flops(d, rows: int, ctx_tokens: int) -> int:
    """One decode step: ``rows`` tokens through every matrix product, plus
    attention over ``ctx_tokens`` summed attended positions."""
    return 2 * matmul_weights(d) * rows + attention_flops(d, ctx_tokens)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flop_s"], nbytes / peak["hbm_byte_s"])
