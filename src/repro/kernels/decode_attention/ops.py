"""jit'd wrapper: GQA flash-decode. The query token's GQA group becomes the
kernel's row dimension (classic flash-decoding layout), so the MXU sees a
(G x Dk) x (Dk x block_k) matmul per KV block instead of a GEMV."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import flash_attention_partial, merge_partials
from repro.kernels.decode_attention.kernel import paged_flash_decode


@partial(jax.jit, static_argnames=("scale", "window", "interpret", "block_k"))
def decode_attention(q, k_cache, v_cache, cache_pos, q_pos, *, scale,
                     window=0, interpret=None, block_k=512):
    """Same signature/semantics as ref.decode_attention_ref (docs there)."""
    B, H, G, Dk = q.shape
    qpos_rows = jnp.broadcast_to(q_pos[:, None], (B, G))
    part = flash_attention_partial(
        q, k_cache, v_cache, qpos_rows, cache_pos, scale=scale, causal=True,
        window=window, block_q=max(8, G), block_k=block_k,
        interpret=interpret)
    return merge_partials([part])


@partial(jax.jit, static_argnames=("scale", "window", "interpret", "block_k"))
def decode_attention_slots(q, k_cache, v_cache, cache_pos, q_pos, slot_idx,
                           *, scale, window=0, interpret=None, block_k=512):
    """Slot-indexed flash decode: the KV cache holds a resident slot
    *pool* (batch axis S_pool >= B) and only rows `slot_idx` (B,) are
    attended — the read-side counterpart of the model's in-place
    slot-indexed cache writes. The gather stays inside the jitted
    program (XLA fuses it into the block streaming), so the Pallas
    kernel itself is unchanged and the fast path remains usable on the
    slot-resident serving cache.

    q: (B, Hkv, G, Dk); k_cache/v_cache: (S_pool, Hkv, C, Dk/Dv);
    cache_pos: (S_pool, C); q_pos: (B,); slot_idx: (B,) int32.
    """
    k = jnp.take(k_cache, slot_idx, axis=0)
    v = jnp.take(v_cache, slot_idx, axis=0)
    cp = jnp.take(cache_pos, slot_idx, axis=0)
    return decode_attention(q, k, v, cp, q_pos, scale=scale, window=window,
                            interpret=interpret, block_k=block_k)


@partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def decode_attention_paged(q, k_pages, v_pages, page_pos, q_pos,
                           block_tables, *, scale, window=0, interpret=None):
    """Paged flash decode: the KV cache is a physical page *pool*
    (DESIGN.md §2.8) and each request reads only the pages named by its
    block table. Unlike `decode_attention_slots` (where XLA gathers the
    resident rows), the block table here is a scalar-prefetch operand
    and the Pallas grid walks it directly — the kernel never touches
    unmapped pages, so decode-read traffic scales with tokens *held*,
    not pool capacity.

    q: (B, Hkv, G, Dk); k_pages/v_pages: (P, Hkv, ps, Dk/Dv);
    page_pos: (P, ps) absolute positions (-1 = empty row, exact no-op);
    q_pos: (B,); block_tables: (B, n_view) int32 physical page ids
    (point unmapped entries at a NULL page whose page_pos is all -1).
    Returns (B, Hkv, G, Dv) f32, matching `decode_attention_paged_ref`.
    """
    part = paged_flash_decode(q, k_pages, v_pages, page_pos, q_pos,
                              block_tables, scale=scale, window=window,
                              interpret=interpret)
    return merge_partials([part])
