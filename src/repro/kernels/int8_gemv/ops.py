"""Jit'd entry points for the fused int8 dequant-GEMV.

Two paths behind one contract (y = (x @ w8) * scale, f32 accumulate):

* :func:`int8_gemv` — the Pallas TPU kernel (interpreted off-TPU),
  padding arbitrary shapes to the int8 tile grid. Bitwise-equal to
  `ref.int8_gemv_ref` on tile-aligned shapes (K % 32, N % 128, the
  wrapper pads B); padded-K shapes are allclose (the zero-padded tail
  can reorder the SIMD reduction).
* :func:`int8_gemv_xla` — a K-blocked `lax.scan` formulation for
  hosts without a TPU lowering: each int8 block dequantizes into a
  cache-resident f32 tile, so HBM traffic stays ~1 byte/weight instead
  of the materialized-convert 4 bytes XLA:CPU emits for a plain
  dequant-then-dot. This is the path `benchmarks/kernel_bench.py`
  times against the bf16 dense matvec.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.int8_gemv.kernel import int8_gemv_call


def _pad_axis(a, mult, axis):
    pad = (-a.shape[axis]) % mult
    if not pad:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@partial(jax.jit, static_argnames=("block_n", "interpret"))
def int8_gemv(x, w8, scale, *, block_n: int = 128, interpret=None):
    """Fused dequant-GEMV: x (B, K) float, w8 (K, N) int8, scale (N,)
    or (1, N) f32 per-output-channel. Returns (B, N) f32."""
    B, K = x.shape
    N = w8.shape[1]
    scale = scale.reshape(1, N)
    xp = _pad_axis(_pad_axis(x, 8, 0), 32, 1)
    wp = _pad_axis(_pad_axis(w8, 32, 0), block_n, 1)
    sp = _pad_axis(scale, block_n, 1)
    out = int8_gemv_call(xp, wp, sp, block_n=block_n, interpret=interpret)
    return out[:B, :N]


@partial(jax.jit, static_argnames=("block_k",))
def int8_gemv_xla(x, w8, scale, *, block_k: int = 128):
    """K-blocked XLA formulation (CPU-friendly, see module docstring).

    Accumulation order differs from the single-dot oracle (per-block
    partial sums), so this path is allclose — not bitwise — to
    `ref.int8_gemv_ref`.
    """
    B, K = x.shape
    N = w8.shape[1]
    scale = scale.reshape(1, N)
    xp = _pad_axis(x.astype(jnp.float32), block_k, 1)
    wp = _pad_axis(w8, block_k, 0)
    nb = xp.shape[1] // block_k

    def body(acc, i):
        blk = jax.lax.dynamic_slice_in_dim(wp, i * block_k, block_k, 0)
        xb = jax.lax.dynamic_slice_in_dim(xp, i * block_k, block_k, 1)
        return acc + xb @ blk.astype(jnp.float32), None

    acc, _ = jax.lax.scan(body, jnp.zeros((B, N), jnp.float32),
                          jnp.arange(nb))
    return acc * scale
