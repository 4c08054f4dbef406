"""Pallas TPU kernel: fused int8 dequant + GEMV for drafter decode.

The decode matvec at drafter batch sizes (B <= 8) is memory-roofline-
bound on the weight stream (DESIGN.md §3.2): the win of weight-only
int8 is that HBM reads halve, *provided the dequant never round-trips
through memory*. This kernel streams int8 weight tiles into VMEM,
converts to f32 in-register, reduces over the full K axis with one MXU
dot, and applies the per-output-channel scale to the accumulator —
the activation block (B x K, small at decode shapes) stays resident in
VMEM across the whole grid.

Grid: 1-D over output tiles (N / block_n). K is NOT tiled: a single
dot per tile keeps the reduction order identical to the pure-jnp
oracle (`ref.int8_gemv_ref`), making kernel-vs-oracle comparisons
bitwise on tile-aligned shapes. Drafter d_ff/d_model sizes comfortably
fit a full (K, block_n) int8 tile in VMEM (K=4096, bn=128 -> 512 KiB).

Tiling constraints (TPU int8 min tile (32, 128)): K % 32 == 0,
block_n % 128 == 0, B padded to 8 by the op wrapper.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import use_interpreter


def _int8_gemv_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)        # (B, K) activations
    w = w_ref[...].astype(jnp.float32)        # (K, bn) int8 -> f32 in-reg
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    o_ref[...] = y * s_ref[...]               # (1, bn) scale broadcast


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def int8_gemv_call(x, w8, scale, *, block_n: int = 128,
                   interpret=None):
    """Raw pallas_call on pre-padded operands.

    x: (B, K) float; w8: (K, N) int8; scale: (1, N) f32 with
    K % 32 == 0, N % block_n == 0 and block_n % 128 == 0 (the op
    wrapper in ops.py pads arbitrary shapes). Returns (B, N) f32.
    """
    B, K = x.shape
    N = w8.shape[1]
    assert w8.shape[0] == K and scale.shape == (1, N)
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    return pl.pallas_call(
        _int8_gemv_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, K), lambda i: (0, 0)),          # x resident
            pl.BlockSpec((K, block_n), lambda i: (0, i)),    # int8 stream
            pl.BlockSpec((1, block_n), lambda i: (0, i)),    # scales
        ],
        out_specs=pl.BlockSpec((B, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
        interpret=use_interpreter(interpret),
    )(x, w8, scale)
