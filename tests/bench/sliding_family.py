"""A family that only the tests know: the dense decoder with every layer's
attention cut to the last `sliding_window` positions, in a layout of its
own (one dict per layer, q/k/v and gate/up each fused into one matrix).

It stands for a family that a later configuration adds as new files: the
harness finds it by name (`bench.families.sliding`, which the test binds
to this module) and names it nowhere. It imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.families import dense
from bench.reference import HIGHEST, _fp8, _mm, _rms, _rope
from bench.roofline import least_seconds

REQUIRED = dense.REQUIRED + ("sliding_window",)


@jax.jit
def _fuse(w):
    """The dense layout's stacked layers -> one dict per layer."""
    layers = []
    for i in range(w["wq"].shape[0]):
        blk = {"norm1": w["ln1"][i],
               "qkv": jnp.concatenate([w["wq"][i], w["wk"][i], w["wv"][i]],
                                      axis=1),
               "o": w["wo"][i], "norm2": w["ln2"][i],
               "gate_up": jnp.concatenate([w["wg"][i], w["wu"][i]], axis=1),
               "down": w["wd"][i]}
        if "bq" in w:
            blk["qkv_bias"] = jnp.concatenate([w["bq"][i], w["bk"][i],
                                               w["bv"][i]])
        layers.append(blk)
    return layers


def plant(key, queries, strength, codes, model: dict, agreement: dict):
    """The dense family's planted weights, laid out one dict per layer."""
    w = dense.plant(key, queries, strength, codes, model, agreement)
    out = {k: v for k, v in w.items() if k != "layers"}
    out["blocks"] = _fuse(w["layers"])
    return out


def _block(x, w, m, fp8):
    T = x.shape[0]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    f = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(T)
    qkv = _mm(_rms(x, f(w["norm1"]), m["norm_eps"]), f(w["qkv"]), fp8)
    if m["qkv_bias"]:
        qkv = qkv + f(w["qkv_bias"])
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=1)
    q = _rope(q.reshape(T, hq, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(T, hkv, hd), pos, m["rope_theta"])
    v = v.reshape(T, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * hd ** -0.5
    back = pos[:, None] - pos[None, :]
    seen = (back >= 0) & (back < m["sliding_window"])
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    if fp8:
        p = _fp8(p, -1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, hq * hd), f(w["o"]), fp8)
    gu = _mm(_rms(x, f(w["norm2"]), m["norm_eps"]), f(w["gate_up"]), fp8)
    g, u = jnp.split(gu, 2, axis=1)
    return x + _mm(jax.nn.silu(g) * u, f(w["down"]), fp8)


def forward(weights, m: dict, tokens, fp8: bool):
    """Float32 logits (T, vocab) of one sequence, layer by layer."""
    f = lambda a: a.astype(jnp.float32)
    x = f(weights["embed"][tokens])
    for w in weights["blocks"]:
        x = _block(x, w, m, fp8)
    x = _rms(x, f(weights["final_norm"]), m["norm_eps"])
    head = (weights["embed"].T if m["tie_embeddings"]
            else weights["head"])[:, : m["vocab"]]
    return _mm(x, f(head), fp8)


def weight_bytes(m: dict, dtype_bytes: int = 2) -> int:
    return dense.weight_bytes(m, dtype_bytes)


def token_flops(m: dict, context: int) -> float:
    """Dense operations with attention over at most the window's keys."""
    seen = min(context + 1, m["sliding_window"])
    return dense.token_flops(m, seen - 1)


def call_least_seconds(m: dict, tokens_at: list, peak: dict):
    return least_seconds(sum(token_flops(m, c) for c in tokens_at),
                         weight_bytes(m), peak)
