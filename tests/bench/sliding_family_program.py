"""The test-only sliding family's adapter: the program serves it as
`attention="swa"` with the family's window."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from bench.families import dense_program


def model_config(m: dict):
    return dataclasses.replace(dense_program.model_config(m),
                               attention="swa",
                               sliding_window=int(m["sliding_window"]))


def to_dense(canon: dict, m: dict) -> dict:
    """One dict per layer, fused -> the dense family's layout."""
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    cut = [hq * hd, (hq + hkv) * hd]
    blocks = canon["blocks"]
    stack = lambda f: jnp.stack([f(b) for b in blocks])
    w = {"ln1": stack(lambda b: b["norm1"]),
         "wq": stack(lambda b: jnp.split(b["qkv"], cut, axis=1)[0]),
         "wk": stack(lambda b: jnp.split(b["qkv"], cut, axis=1)[1]),
         "wv": stack(lambda b: jnp.split(b["qkv"], cut, axis=1)[2]),
         "wo": stack(lambda b: b["o"]),
         "ln2": stack(lambda b: b["norm2"]),
         "wg": stack(lambda b: jnp.split(b["gate_up"], 2, axis=1)[0]),
         "wu": stack(lambda b: jnp.split(b["gate_up"], 2, axis=1)[1]),
         "wd": stack(lambda b: b["down"])}
    if "qkv_bias" in blocks[0]:
        w["bq"] = stack(lambda b: jnp.split(b["qkv_bias"], cut)[0])
        w["bk"] = stack(lambda b: jnp.split(b["qkv_bias"], cut)[1])
        w["bv"] = stack(lambda b: jnp.split(b["qkv_bias"], cut)[2])
    dense = {k: v for k, v in canon.items() if k != "blocks"}
    dense["layers"] = w
    return dense


def program_params(canon: dict, m: dict) -> dict:
    return dense_program.program_params(to_dense(canon, m), m)
