"""Plain reference of the served models: a decoder-only transformer as the
Qwen1.5/Qwen2/Llama model cards describe it, in float32 `jax.numpy`.

Pre-norm blocks: RMSNorm -> grouped-query attention with rotary position
(rotate-half form, base `rope_theta`), optional q/k/v bias, causal mask
-> residual; RMSNorm -> SwiGLU MLP -> residual; final RMSNorm and the
head (the embedding transposed when tied). No cache, no batching tricks,
no kernels: one full forward over a sequence, layer by layer.

It reads the benchmark's own weight layout (`agreement.canonical_weights`)
and imports nothing of the program under test.

`precision="fp8"` is the control: the same forward with both operands of
every matrix product rounded to float8 e4m3 (a scale per row of the
activations and per output channel of the weights), accumulated in
float32 - the lower precision that a later change could be tempted by.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, fp8: bool):
    """a (..., K) @ b (K, N) in float32 at full precision, or with both
    operands rounded to fp8 first (rows of a, columns of b)."""
    if fp8:
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (T, H, D): rotate-half rotary embedding at positions pos (T,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * cos + rot * sin


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


def _model_key(m):
    return tuple(sorted((k, m[k]) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab", "qkv_bias", "tie_embeddings", "rope_theta", "norm_eps")))


@partial(jax.jit, static_argnames=("mkey", "fp8"))
def _logits(weights, tokens, *, mkey, fp8):
    m = dict(mkey)
    f = lambda a: a.astype(jnp.float32)
    x = f(weights["embed"][tokens])

    def body(x, w):
        return _layer(x, w, m, fp8), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _rms(x, f(weights["final_norm"]), m["norm_eps"])
    head = (weights["embed"].T if m["tie_embeddings"]
            else weights["head"])[:, : m["vocab"]]
    return _mm(x, f(head), fp8)


@partial(jax.jit, static_argnames=("mkey", "control"))
def _readings(weights, tokens, pick, *, mkey, control):
    ref = _logits(weights, tokens, mkey=mkey, fp8=False)
    best = jnp.max(ref, -1)
    picked = jnp.take_along_axis(ref, jnp.maximum(pick, 0)[:, None], -1)[:, 0]
    out = {"best": best, "picked": picked, "argmax": jnp.argmax(ref, -1)}
    if control:
        low = _logits(weights, tokens, mkey=mkey, fp8=True)
        a = jnp.argmax(low, -1)
        out["control_gap"] = best - jnp.take_along_axis(ref, a[:, None],
                                                        -1)[:, 0]
    return out


def readings(weights, model: dict, tokens, pick, control: bool = False):
    """One sequence `tokens` (T,) through the reference. At every position
    t: the best logit of the next token, the logit of `pick[t]`, the
    argmax, and with `control` the gap below the best of the token that
    the fp8 control puts first. Pad `tokens` to a fixed length so that one
    program serves every request (the mask is causal)."""
    out = _readings(weights, jnp.asarray(tokens, jnp.int32),
                    jnp.asarray(pick, jnp.int32), mkey=_model_key(model),
                    control=control)
    return jax.device_get(out)


def greedy(weights, model: dict, prompt, n: int, length: int):
    """The reference's own greedy continuation of `prompt` by n tokens,
    recomputing the whole sequence each step (tests only)."""
    seq = list(prompt)
    for _ in range(n):
        toks = seq + [0] * (length - len(seq))
        r = readings(weights, model, toks, [0] * length)
        seq.append(int(r["argmax"][len(seq) - 1]))
    return seq[len(prompt):]
