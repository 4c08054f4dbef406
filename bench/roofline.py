"""Operations and bytes of the served models' calls, from their shapes.

Counts use the published vocabulary (never the padded one) and only the
work a call needs: no padding rows, no rejected or padded tree nodes. A
matrix product of (m, k) by (k, n) is 2mkn operations.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in {PEAKS.name}")
    return table[device_kind]


def layer_params(m: dict) -> int:
    """Parameters of one decoder layer (attention, biases, MLP, norms)."""
    d, hq, hkv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"])
    attn = d * hq * hd * 2 + d * hkv * hd * 2
    bias = (hq * hd + 2 * hkv * hd) if m["qkv_bias"] else 0
    return attn + bias + 3 * d * ff + 2 * d


def weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Bytes of every weight a forward reads once: layers, final norm and
    head (the embedding rows a call gathers are negligible)."""
    return dtype_bytes * (m["n_layers"] * layer_params(m) + m["d_model"]
                          + m["d_model"] * m["vocab"])


def token_flops(m: dict, context: int) -> float:
    """Forward operations for one token at position `context` (it attends
    to context + 1 keys): the layers' matrix products, attention scores
    and values, and the head over the published vocabulary."""
    d, hq, hkv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"])
    mats = 2 * (d * hq * hd * 2 + d * hkv * hd * 2 + 3 * d * ff)
    attn = 4 * hq * hd * (context + 1)
    return m["n_layers"] * (mats + attn) + 2 * d * m["vocab"]


def call_least_seconds(m: dict, tokens_at: list, peak: dict):
    """Least time of one forward over tokens at the given positions:
    (seconds, bound) with bound 'compute' or 'memory' (the weights read
    once, the only bytes every such call must move)."""
    flops = sum(token_flops(m, c) for c in tokens_at)
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = weight_bytes(m) / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
