"""Shared Pallas flash-attention machinery and the interpreter choice.

The kernels compile for the TPU (Mosaic). On any other backend they run
in the Pallas interpreter, which the CPU tests use to check them against
their jnp oracles; `use_interpreter` makes that choice for every kernel.

One partial-softmax flash kernel covers the framework's attention hot
spots; wrappers in tree_attention/ and decode_attention/ specialize block
shapes and compose partials (cache + draft-tree segment merge — the
flash-decoding trick generalized to CoSine's tree verification).

The kernel emits *unnormalized* (acc, m, l) so multiple KV sources can be
merged exactly before the final normalization (see merge_partials).

TPU layout: the last two dims of every block must be multiples of
(8, 128) or span the array. Query positions and the m/l statistics
therefore carry a trailing unit axis ((rows, 1) blocks) and key
positions a middle unit axis ((1, block_k) blocks), so the row axis
lies on sublanes and the key axis on lanes.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def use_interpreter(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter: an explicit
    `interpret` wins; None means compiled on a TPU backend and
    interpreted everywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _make_kernel(*, scale, causal, window, nk, has_mask, block_q, dv,
                 kv_axis=3, n_prefetch=0):
    """Flash-attention body over one (block_q x block_k) tile. The KV
    block index is grid axis `kv_axis` (sequential); the first
    `n_prefetch` refs are scalar-prefetch operands that only feed the
    BlockSpec index maps."""
    def kernel(*refs):
        qpos_ref, kpos_ref, q_ref, k_ref, v_ref = refs[n_prefetch:
                                                       n_prefetch + 5]
        rest = refs[n_prefetch + 5:]
        mask_ref = rest[0] if has_mask else None
        acc_out, m_out, l_out, m_s, l_s, acc_s = rest[int(has_mask):]
        kb = pl.program_id(kv_axis)

        @pl.when(kb == 0)
        def _init():
            m_s[...] = jnp.full((block_q, 1), NEG_INF, jnp.float32)
            l_s[...] = jnp.zeros((block_q, 1), jnp.float32)
            acc_s[...] = jnp.zeros((block_q, dv), jnp.float32)

        q = q_ref[0, 0].astype(jnp.float32)          # (bq, Dk)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, Dk)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, Dv)
        qpos = qpos_ref[0]                           # (bq, 1)
        kpos = kpos_ref[0]                           # (1, bk)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        valid = kpos >= 0
        if causal:
            valid = valid & (kpos <= qpos)
        if window > 0:
            valid = valid & (qpos - kpos < window)
        if mask_ref is not None:
            valid = valid & (mask_ref[0] != 0)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_s[...] = m_new

        @pl.when(kb == nk - 1)
        def _out():
            acc_out[0, 0] = acc_s[...].astype(acc_out.dtype)
            m_out[0, 0] = m_s[...]
            l_out[0, 0] = l_s[...]

    return kernel


def flash_scratch(block_q, dv):
    """VMEM carries (m, l, acc) of the online softmax across KV blocks."""
    return [pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32)]


def _pad_to(x, size, axis, value=0):
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg, constant_values=value)


def flash_attention_partial(q, k, v, q_pos, k_pos, *, scale, causal=True,
                            window=0, mask=None, block_q=128, block_k=128,
                            interpret=None):
    """Blocked flash attention returning unnormalized partials.

    q: (B, Hkv, R, Dk) — R query rows (tokens x GQA group, pre-expanded)
    k: (B, Hkv, S, Dk); v: (B, Hkv, S, Dv)
    q_pos: (B, R); k_pos: (B, S); mask: optional (B, R, S) bool
    Returns acc (B, Hkv, R, Dv) f32, m (B, Hkv, R) f32, l (B, Hkv, R) f32.
    On a TPU, block_k must be a multiple of 128 unless it covers S.
    """
    B, H, R, Dk = q.shape
    S = k.shape[2]
    Dv = v.shape[3]
    block_q = max(8, min(block_q, R))
    block_k = max(8, min(block_k, S))
    Rp = math.ceil(R / block_q) * block_q
    Sp = math.ceil(S / block_k) * block_k

    q = _pad_to(q, Rp, 2)
    k = _pad_to(k, Sp, 2)
    v = _pad_to(v, Sp, 2)
    q_pos = _pad_to(q_pos.astype(jnp.int32), Rp, 1)[:, :, None]
    k_pos = _pad_to(k_pos.astype(jnp.int32), Sp, 1, value=-1)[:, None, :]
    if mask is not None:
        mask = _pad_to(_pad_to(mask.astype(jnp.int32), Rp, 1), Sp, 2)

    nq, nk = Rp // block_q, Sp // block_k
    grid = (B, H, nq, nk)

    in_specs = [
        pl.BlockSpec((1, block_q, 1), lambda b, h, iq, ik: (b, iq, 0)),
        pl.BlockSpec((1, 1, block_k), lambda b, h, iq, ik: (b, 0, ik)),
        pl.BlockSpec((1, 1, block_q, Dk), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, Dk), lambda b, h, iq, ik: (b, h, ik, 0)),
        pl.BlockSpec((1, 1, block_k, Dv), lambda b, h, iq, ik: (b, h, ik, 0)),
    ]
    args = [q_pos, k_pos, q, k, v]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_q, block_k),
                                     lambda b, h, iq, ik: (b, iq, ik)))
        args.append(mask)

    out_specs = [
        pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_q, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, Rp, Dv), jnp.float32),
        jax.ShapeDtypeStruct((B, H, Rp, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, H, Rp, 1), jnp.float32),
    ]

    kernel = _make_kernel(scale=scale, causal=causal, window=window, nk=nk,
                          has_mask=mask is not None, block_q=block_q, dv=Dv)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=flash_scratch(block_q, Dv),
        interpret=use_interpreter(interpret),
    )(*args)
    return acc[:, :, :R], m[:, :, :R, 0], l[:, :, :R, 0]


def merge_partials(parts):
    """Exactly merge [(acc, m, l), ...] partials; returns normalized out."""
    acc, m, l = parts[0]
    for acc2, m2, l2 in parts[1:]:
        m_new = jnp.maximum(m, m2)
        e1 = jnp.exp(m - m_new)
        e2 = jnp.exp(m2 - m_new)
        acc = acc * e1[..., None] + acc2 * e2[..., None]
        l = l * e1 + l2 * e2
        m = m_new
    l = jnp.where(l == 0.0, 1.0, l)
    return acc / l[..., None]
