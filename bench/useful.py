"""The useful work of a call, from its shapes, and the chip's peaks.

The counts are of what the task needs, not of what an implementation
does: a fused or removed stage leaves them unchanged, so a share of the
roofline stays at or under 100% whatever runs the work.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind not in the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def reduce_bytes(rows: int, width: int, segments: int) -> int:
    """A segmented f32 sum reads its values and int32 labels once and
    writes the (segments, width) f32 result: N*D*4 + N*4 + S*D*4."""
    return rows * width * 4 + rows * 4 + segments * width * 4


def matmul_params(model: dict) -> int:
    """Parameters that enter a matrix product per token: the attention
    projections, the gated MLP and the output head (the embedding table is
    a row lookup)."""
    d, f = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return model["num_hidden_layers"] * (attn + 3 * d * f) \
        + d * model["vocab_size"]


def kv_bytes_per_token(model: dict, itemsize: int = 2) -> int:
    """Keys and values of one token over every layer."""
    hd = model["hidden_size"] // model["num_attention_heads"]
    return 2 * model["num_hidden_layers"] * model["num_key_value_heads"] \
        * hd * itemsize


def decode_step(model: dict, active: int, live_tokens: int,
                itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one decode step needs: 2 x params x active slots,
    and the weights once plus the KV of the live tokens of the active
    slots."""
    p = matmul_params(model)
    return (2 * p * active,
            p * itemsize + live_tokens * kv_bytes_per_token(model, itemsize))


def model_flops(model: dict, tokens: int) -> int:
    """Forward FLOPs of ``tokens`` tokens: 2 x matmul params each."""
    return 2 * matmul_params(model) * tokens
