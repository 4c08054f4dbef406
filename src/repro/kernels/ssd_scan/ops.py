"""jit'd wrapper for the SSD Pallas kernel — same API as
`repro.models.ssm.ssd_chunked` so `ssm_mixer(use_kernel=True)` swaps it in."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


@partial(jax.jit, static_argnames=("chunk", "interpret", "head_block"))
def ssd(x, dt, A, B, C, chunk=128, initial_state=None, *,
        head_block: int = 8, interpret=None):
    """x: (b, L, H, P); dt: (b, L, H); A: (H,); B/C: (b, L, G, N).
    Returns (y (b, L, H, P), final_state (b, H, P, N))."""
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    chunk = min(chunk, L)
    rep = H // G
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)
    if initial_state is None:
        initial_state = jnp.zeros((b, H, P, N), jnp.float32)

    pad = (-L) % chunk
    dt = dt.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Lp = L + pad

    # head-major operands; the chunk-local cumulative decay is a cheap
    # XLA op here so the kernel needs no in-register prefix sum
    dt_h = dt.transpose(0, 2, 1)                              # (b, H, Lp)
    dA_cum = jnp.cumsum(
        (dt_h * A.astype(jnp.float32)[None, :, None]).reshape(
            b, H, Lp // chunk, chunk), axis=-1).reshape(b, H, Lp)

    hb = head_block
    while H % hb:
        hb //= 2
    y, final = ssd_scan_pallas(
        x.transpose(0, 2, 1, 3), dt_h, dA_cum, Bh.transpose(0, 2, 3, 1),
        Ch.transpose(0, 2, 1, 3), chunk,
        initial_state.astype(jnp.float32).transpose(0, 1, 3, 2),
        head_block=max(hb, 1), interpret=interpret)
    return y.transpose(0, 2, 1, 3)[:, :L], final.transpose(0, 1, 3, 2)
