"""Flash-decode Pallas kernels (TPU): the dense `pl.pallas_call` +
BlockSpec construction lives in `repro.kernels.common
.flash_attention_partial` (shared with tree_attention). This module pins
the decode specializations:

* dense/slot decode — the GQA group is the row dimension (q block =
  (G, Dk), G padded to 8), KV streams in long blocks (default 512) to
  maximize HBM read efficiency — the decode step is memory-roofline-
  bound (DESIGN.md §3.2).
* paged decode (`paged_flash_decode`) — the KV cache is a page *pool*
  (DESIGN.md §2.8) and each request's block table is a scalar-prefetch
  operand: the grid walks (batch, head, logical page) and the BlockSpec
  index maps dereference `tbl[b, lp]` to stream exactly the pages the
  request holds, so per-step HBM traffic is ∝ tokens held, never pool
  capacity, with no gather materialized outside the kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (_make_kernel, _pad_to, flash_attention_partial,
                                  flash_scratch, merge_partials,
                                  use_interpreter)

__all__ = ["flash_attention_partial", "merge_partials", "_make_kernel",
           "paged_flash_decode"]


def paged_flash_decode(q, k_pages, v_pages, page_pos, q_pos, block_tables,
                       *, scale, window=0, interpret=None):
    """Decode-over-pool flash attention partials (unnormalized).

    q: (B, Hkv, G, Dk) one token's queries (G = GQA group rows);
    k_pages/v_pages: (P, Hkv, ps, Dk/Dv) physical page pool;
    page_pos: (P, ps) absolute position stored in each pool row (-1 =
    empty — NULL/unwritten pages mask to exact no-ops);
    q_pos: (B,); block_tables: (B, n_view) int32 physical page ids.

    The block table is a scalar-prefetch operand: the grid's last axis
    is the *logical* page index and the k/v/page_pos BlockSpec index
    maps dereference `tbl[b, lp]`, so the kernel streams only each
    request's mapped pages — the decode-read traffic is n_view * ps
    columns per request regardless of pool size P.

    Returns (acc (B, Hkv, G, Dv) f32, m (B, Hkv, G), l (B, Hkv, G));
    normalize with `merge_partials` (optionally merging a fresh-segment
    partial first, as tree verification does).
    """
    B, H, G, Dk = q.shape
    P, ps = page_pos.shape
    Dv = v_pages.shape[3]
    nv = block_tables.shape[1]
    bq = max(8, G)

    q = _pad_to(q, bq, 2)
    qpos_rows = jnp.broadcast_to(q_pos.astype(jnp.int32)[:, None, None],
                                 (B, bq, 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, H, nv),
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, h, i, tbl: (b, 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda b, h, i, tbl: (tbl[b, i], 0, 0)),
            pl.BlockSpec((1, 1, bq, Dk), lambda b, h, i, tbl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, ps, Dk),
                         lambda b, h, i, tbl: (tbl[b, i], h, 0, 0)),
            pl.BlockSpec((1, 1, ps, Dv),
                         lambda b, h, i, tbl: (tbl[b, i], h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, tbl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, tbl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, tbl: (b, h, 0, 0)),
        ],
        scratch_shapes=flash_scratch(bq, Dv),
    )
    kernel = _make_kernel(scale=scale, causal=True, window=window, nk=nv,
                          has_mask=False, block_q=bq, dv=Dv, kv_axis=2,
                          n_prefetch=1)
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, bq, Dv), jnp.float32),
            jax.ShapeDtypeStruct((B, H, bq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H, bq, 1), jnp.float32),
        ],
        interpret=use_interpreter(interpret),
    )(block_tables.astype(jnp.int32), qpos_rows,
      page_pos.astype(jnp.int32).reshape(P, 1, ps), q, k_pages, v_pages)
    return acc[:, :, :G], m[:, :, :G, 0], l[:, :, :G, 0]
