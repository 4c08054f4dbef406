"""Plain reference of the served models, in float32 `jax.numpy`.

Each model's forward pass is its family's (`bench/families/<family>.py`
`forward`): one full forward over a sequence, no cache, no batching
tricks, no kernels. It reads the family's own weight layout
(`agreement.canonical_weights`) and imports nothing of the program under
test. This module holds what every family shares: the matrix product at
full precision and its fp8 control, RMSNorm and rotary position, and the
readings that `bench/check.py` compares.

`fp8` is the control: the same forward with both operands of every
matrix product rounded to float8 e4m3 (a scale per row of the activations
and per output channel of the weights), accumulated in float32 - the
lower precision that a later change could be tempted by.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench import families

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


def _model_key(m):
    """The hashable key of one model entry: its family's keys."""
    return tuple(sorted((k, m[k]) for k in families.of(m).REQUIRED))


@partial(jax.jit, static_argnames=("family", "mkey", "fp8"))
def _logits(weights, tokens, *, family, mkey, fp8):
    return families.family(family).forward(weights, dict(mkey), tokens, fp8)


@partial(jax.jit, static_argnames=("family", "mkey", "control"))
def _readings(weights, tokens, pick, *, family, mkey, control):
    ref = _logits(weights, tokens, family=family, mkey=mkey, fp8=False)
    best = jnp.max(ref, -1)
    picked = jnp.take_along_axis(ref, jnp.maximum(pick, 0)[:, None], -1)[:, 0]
    out = {"best": best, "picked": picked, "argmax": jnp.argmax(ref, -1)}
    if control:
        low = _logits(weights, tokens, family=family, mkey=mkey, fp8=True)
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
                    jnp.asarray(pick, jnp.int32), family=model["family"],
                    mkey=_model_key(model), control=control)
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
