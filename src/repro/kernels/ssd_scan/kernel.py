"""Mamba2 SSD chunked-scan Pallas kernel (TPU target).

Layout: grid (B, n_head_blocks, n_chunks); the chunk dimension is
sequential ("arbitrary") and each head's (N, P) recurrent state lives
in VMEM scratch across chunk iterations — the inter-chunk recurrence never
round-trips HBM. Within a chunk the dual ("attention-like") form runs on
the MXU as plain 2-D matmuls per head: (Q x N) x (N x Q) scores and
(Q x Q) x (Q x P) outputs, Q = chunk_size (default 128, MXU-aligned).

Operands are head-major so every block's last two dims tile as (8, 128)
or span the array: x / C as (Q, P) / (Q, N) rows, B pre-transposed to
(N, Q), and dt plus the chunk-local cumulative decay as (Q, 1) columns
and a (1, Q) row (the wrapper computes the cumulative sum).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import use_interpreter


def _make_ssd_kernel(*, Q, hb, nc):
    def kernel(x_ref, dt_ref, acol_ref, arow_ref, bt_ref, c_ref, init_ref,
               y_ref, final_ref, state_s):
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _init():
            state_s[...] = init_ref[0].astype(jnp.float32)

        causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
        for h in range(hb):
            x = x_ref[0, h].astype(jnp.float32)       # (Q, P)
            dt = dt_ref[0, h]                         # (Q, 1)
            acol = acol_ref[0, h]                     # (Q, 1) cum dt*A
            arow = arow_ref[0, h]                     # (1, Q)
            bt = bt_ref[0, h]                         # (N, Q)
            c = c_ref[0, h]                           # (Q, N)
            state = state_s[h]                        # (N, P)

            lmat = jnp.where(causal, jnp.exp(acol - arow), 0.0)
            scores = jnp.dot(c, bt, preferred_element_type=jnp.float32) * lmat
            y = jnp.dot(scores, x * dt, preferred_element_type=jnp.float32)
            y = y + jnp.dot(c, state,
                            preferred_element_type=jnp.float32) * jnp.exp(acol)
            y_ref[0, h] = y.astype(y_ref.dtype)

            last = acol[Q - 1:Q, :]                   # (1, 1)
            w = jnp.exp(last - acol) * dt             # (Q, 1)
            # widen the chunk decay along lanes first: Mosaic cannot
            # broadcast a (1, 1) value over sublanes and lanes at once
            decay = jnp.exp(last + jnp.zeros((1, state.shape[1]),
                                             jnp.float32))
            state_s[h] = state * decay + jnp.dot(
                bt, x * w, preferred_element_type=jnp.float32)

        @pl.when(ci == nc - 1)
        def _final():
            final_ref[0] = state_s[...].astype(final_ref.dtype)

    return kernel


def ssd_scan_pallas(x, dt, dA_cum, Bt, C, chunk, initial_state,
                    head_block: int = 8, interpret=None):
    """Head-major operands (ops.py builds them):
    x: (b, H, L, P); dt: (b, H, L) f32; dA_cum: (b, H, L) f32 — the
    cumulative sum of dt * A restarted at every chunk; Bt: (b, H, N, L)
    and C: (b, H, L, N) f32 (groups pre-broadcast to heads);
    initial_state: (b, H, N, P) f32. L must be a multiple of `chunk`.
    Returns (y (b, H, L, P) in x.dtype, final (b, H, N, P) f32)."""
    b, H, L, P = x.shape
    N = Bt.shape[2]
    hb = min(head_block, H)
    assert H % hb == 0 and L % chunk == 0
    nc = L // chunk
    grid = (b, H // hb, nc)
    Q = chunk

    kernel = _make_ssd_kernel(Q=Q, hb=hb, nc=nc)
    y, final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, Q, P), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, hb, Q, 1), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, hb, Q, 1), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, hb, 1, Q), lambda i, j, c: (i, j, 0, c)),
            pl.BlockSpec((1, hb, N, Q), lambda i, j, c: (i, j, 0, c)),
            pl.BlockSpec((1, hb, Q, N), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, hb, N, P), lambda i, j, c: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, Q, P), lambda i, j, c: (i, j, c, 0)),
            pl.BlockSpec((1, hb, N, P), lambda i, j, c: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((b, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, N, P), jnp.float32)],
        interpret=use_interpreter(interpret),
    )(x, dt[..., None], dA_cum[..., None], dA_cum[:, :, None, :], Bt, C,
      initial_state)
    return y, final
