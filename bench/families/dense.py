"""The dense family: a decoder-only transformer as the Qwen1.5/Qwen2/Llama
model cards describe it.

Pre-norm blocks: RMSNorm -> grouped-query attention with rotary position
(rotate-half form, base `rope_theta`), optional q/k/v bias, causal mask
-> residual; RMSNorm -> SwiGLU MLP -> residual; final RMSNorm and the
head (the embedding transposed when tied).

Layout (`plant`): {"embed" (rows, d), ["head" (d, rows)], "final_norm"
(d,), "layers": {name: (n_layers, ...)}}, all bf16, every tensor at the
shape and dtype the program's `init_params` gives it; only values differ.

Planting. The map of `bench/agreement.py` goes through the first layer's
MLP: the embedding row of v holds its output code c_v (subspace A), its
query q_v (subspace B), a constant gate axis and a filler axis that gives
every row one norm; the MLP (silu gate held open by the constant axis)
replaces A by G * q_v and clears B, the gate axis and the filler, so the
final hidden state points at q_v and the head (the embedding itself when
tied; its transpose when untied) scores every token by c_u . q_v.

Codes and queries carry two more axes. Every code has 1 on the last, and
a query of strength sigma_v has sigma_v * t there (t = `head_offset` /
`gain`), which moves v's logits by sigma_v * t: the best logit of every
map sits near zero, where a float's rounding is smallest, so the check
sees the model's own rounding and not that of large logits. The other
axis takes the rest of the query's norm, sqrt(1 - sigma_v^2) of it, and
no code reads it: after the final norm a drafter off its domain
(sigma_v = `out_strength`) scores every token sigma_v times as far from
zero, so it is less confident there, and its best logit is still near
zero. All other residual-branch output projections (`wo`, `wd`) are
scaled so that together they add a random direction of `branch_noise`
times the residual stream's norm: every layer still computes and still
moves the logits, only less than the planted map.

Reference (`forward`): no cache, no batching tricks, no kernels: one full
forward over a sequence, layer by layer, in float32.

Counts: every weight is read once per call; a matrix product of (m, k)
by (k, n) is 2mkn operations.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.agreement import padded_vocab
from bench.reference import HIGHEST, _fp8, _mm, _rms, _rope
from bench.roofline import least_seconds

REQUIRED = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "qkv_bias", "tie_embeddings", "rope_theta",
            "norm_eps")

GATE = 4.0          # pre-activation of the held-open gate units


# ------------------------------------------------------------ planting

def _dense(key, shape, dtype):
    """`init_params`'s dense draw: N(0, 1/fan_in), fan_in = shape[-2]."""
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(shape[-2])).astype(dtype)


@partial(jax.jit, static_argnames=("spec", "rows", "plant"))
def _weights(key, queries, strength, codes, *, spec, rows, plant):
    (n_layers, d, hq, hkv, hd, ff, vocab, qkv_bias, tied) = spec
    (logit_scale, gain, gate_axis, head_offset, branch_noise) = plant
    dt = jnp.bfloat16
    V = codes.shape[0]
    ks = jax.random.split(key, 12)
    # codes and queries with the offset axis (last) and the axis that
    # keeps every query's norm (second last); see the module's docstring
    t = head_offset / gain
    sig = strength[:, None]
    codes = jnp.concatenate([codes, jnp.zeros((V, 1)), jnp.ones((V, 1))], 1)
    q = jnp.concatenate([sig * queries,
                         jnp.sqrt((1.0 - sig ** 2) * (1.0 + t * t)),
                         sig * t], axis=1)
    R = codes.shape[1]
    # orthonormal frame: A (R), B (R), gate axis, filler axis
    basis, _ = jnp.linalg.qr(jax.random.normal(ks[0], (d, 2 * R + 2)))
    QA, QB = basis[:, :R], basis[:, R: 2 * R]
    e0, e1 = basis[:, 2 * R], basis[:, 2 * R + 1]
    sc = jnp.sum(codes * codes, axis=1)
    sq = jnp.sum(q * q, axis=1)
    total = jnp.max(sc) + jnp.max(sq) + gate_axis ** 2 + 0.25
    fill = jnp.sqrt(total - sc - sq - gate_axis ** 2)
    s = logit_scale / math.sqrt(d)
    emb = s * (codes @ QA.T + q @ QB.T + gate_axis * e0[None, :]
               + fill[:, None] * e1[None, :])
    pad = jax.random.normal(ks[1], (rows - vocab, d)) * (s / math.sqrt(d))
    embed = jnp.concatenate([emb, pad], axis=0).astype(dt)

    L = n_layers
    # every residual branch adds about 1.5 * mult * sqrt(d) of random
    # direction; over L layers that is branch_noise times the stream's norm
    mult = branch_noise * s * jnp.sqrt(total) / (1.5 * math.sqrt(L * d))
    w = {
        "ln1": jnp.ones((L, d), dt),
        "wq": _dense(ks[2], (L, d, hq * hd), dt),
        "wk": _dense(ks[3], (L, d, hkv * hd), dt),
        "wv": _dense(ks[4], (L, d, hkv * hd), dt),
        "wo": (_dense(ks[5], (L, hq * hd, d), jnp.float32)
               * mult).astype(dt),
        "ln2": jnp.ones((L, d), dt),
        "wg": _dense(ks[6], (L, d, ff), dt),
        "wu": _dense(ks[7], (L, d, ff), dt),
    }
    if qkv_bias:
        w["bq"] = jnp.zeros((L, hq * hd), dt)
        w["bk"] = jnp.zeros((L, hkv * hd), dt)
        w["bv"] = jnp.zeros((L, hkv * hd), dt)
    wd = _dense(ks[8], (L, ff, d), jnp.float32) * mult
    # layer 0's MLP: units [0, R) read B and write G*A - B; units [R, 2R)
    # read A and write -A; unit 2R reads the filler axis and clears it.
    # The RMS norm in front scales the row to norm sqrt(d)/sqrt(total);
    # silu(GATE) holds every used unit's gate open by the same factor.
    lam = math.sqrt(d) / jnp.sqrt(total)
    act = GATE * jax.nn.sigmoid(GATE)
    # unit 2R+1 reads the gate axis and clears it
    n_used = 2 * R + 2
    read = jnp.concatenate([QB, QA, e1[:, None], e0[:, None]], axis=1)
    write = jnp.concatenate(
        [gain * QA.T - QB.T, -QA.T, -e1[None, :], -e0[None, :]],
        axis=0) * (s / (lam * act))                                # (n, d)
    gate = jnp.broadcast_to((GATE / (lam * gate_axis)) * e0[:, None],
                            (d, n_used))
    w["wu"] = w["wu"].at[0, :, :n_used].set(read.astype(dt))
    w["wg"] = w["wg"].at[0, :, :n_used].set(gate.astype(dt))
    wd = wd.at[0, :n_used, :].set(write)
    w["wd"] = wd.astype(dt)
    out = {"embed": embed, "layers": w, "final_norm": jnp.ones((d,), dt)}
    if not tied:
        out["head"] = embed.T
    return out


def spec_tuple(m: dict):
    """Hashable sizes of one model entry of a configuration file."""
    return (int(m["n_layers"]), int(m["d_model"]), int(m["n_heads"]),
            int(m["n_kv_heads"]), int(m["head_dim"]), int(m["d_ff"]),
            int(m["vocab"]), bool(m["qkv_bias"]), bool(m["tie_embeddings"]))


def plant_tuple(agreement: dict):
    """Hashable planting constants of a configuration."""
    return (float(agreement["logit_scale"]), float(agreement["gain"]),
            float(agreement["gate_axis"]), float(agreement["head_offset"]),
            float(agreement["branch_noise"]))


def plant(key, queries, strength, codes, model: dict, agreement: dict):
    """The model's weights in the dense layout, in one jitted call."""
    return _weights(key, queries, strength, codes, spec=spec_tuple(model),
                    rows=padded_vocab(int(model["vocab"])),
                    plant=plant_tuple(agreement))


# ------------------------------------------------------------ reference

def _layer(x, w, m, fp8):
    T = x.shape[0]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    f = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(T)
    h = _rms(x, f(w["ln1"]), m["norm_eps"])
    q = _mm(h, f(w["wq"]), fp8)
    k = _mm(h, f(w["wk"]), fp8)
    v = _mm(h, f(w["wv"]), fp8)
    if m["qkv_bias"]:
        q, k, v = q + f(w["bq"]), k + f(w["bk"]), v + f(w["bv"])
    q = _rope(q.reshape(T, hq, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(T, hkv, hd), pos, m["rope_theta"])
    v = v.reshape(T, hkv, hd)
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if fp8:
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if fp8:
        p = _fp8(p, -1)
    o = jnp.einsum("hts,shd->thd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, hq * hd), f(w["wo"]), fp8)
    h = _rms(x, f(w["ln2"]), m["norm_eps"])
    a = jax.nn.silu(_mm(h, f(w["wg"]), fp8)) * _mm(h, f(w["wu"]), fp8)
    return x + _mm(a, f(w["wd"]), fp8)


def forward(weights, m: dict, tokens, fp8: bool):
    """Float32 logits (T, vocab) of one sequence `tokens` (T,)."""
    f = lambda a: a.astype(jnp.float32)
    x = f(weights["embed"][tokens])

    def body(x, w):
        return _layer(x, w, m, fp8), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _rms(x, f(weights["final_norm"]), m["norm_eps"])
    head = (weights["embed"].T if m["tie_embeddings"]
            else weights["head"])[:, : m["vocab"]]
    return _mm(x, f(head), fp8)


# ------------------------------------------------------------ counts

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
    """Least time of one forward over tokens at the given positions: the
    operations of those tokens, or the weights read once (the only bytes
    every such call must move), whichever bounds."""
    return least_seconds(sum(token_flops(m, c) for c in tokens_at),
                         weight_bytes(m), peak)
