"""Operations and bytes of the served models' calls, from their shapes.

Counts use the published vocabulary (never the padded one) and only the
work a call needs: no padding rows, no rejected or padded tree nodes. A
matrix product of (m, k) by (k, n) is 2mkn operations. Each model entry's
family counts its own (`bench/families/<family>.py`); this module holds
the chip's peaks and dispatches.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import families

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in {PEAKS.name}")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict):
    """(seconds, bound) of a call that must do `flops` operations and move
    `nbytes` bytes: bound 'compute' or 'memory', whichever takes longer."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def layer_params(m: dict) -> int:
    """Parameters of one decoder layer, for a family whose layers are all
    alike."""
    return families.of(m).layer_params(m)


def weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """Bytes of the weights one forward call reads."""
    return families.of(m).weight_bytes(m, dtype_bytes)


def token_flops(m: dict, context: int) -> float:
    """Forward operations for one token at position `context` (it attends
    to context + 1 keys), the head over the published vocabulary."""
    return families.of(m).token_flops(m, context)


def call_least_seconds(m: dict, tokens_at: list, peak: dict):
    """Least time of one forward over tokens at the given positions:
    (seconds, bound) with bound 'compute' or 'memory'."""
    return families.of(m).call_least_seconds(m, tokens_at, peak)
