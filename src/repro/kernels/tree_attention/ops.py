"""jit'd wrapper: tree-attention verification = flash partial over the KV
cache merged with a masked flash partial over the fresh tree segment."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import flash_attention_partial, merge_partials


@partial(jax.jit, static_argnames=("scale", "window", "interpret",
                                   "block_q", "block_k"))
def tree_attention(q, k_cache, v_cache, cache_pos, k_seg, v_seg, q_pos,
                   seg_mask, *, scale, window=0, interpret=None,
                   block_q=128, block_k=128):
    """Same signature/semantics as ref.tree_attention_ref (docs there)."""
    hist = flash_attention_partial(
        q, k_cache, v_cache, q_pos, cache_pos, scale=scale, causal=True,
        window=window, block_q=block_q, block_k=block_k, interpret=interpret)
    seg_pos = jnp.zeros(k_seg.shape[:1] + k_seg.shape[2:3], jnp.int32)
    seg = flash_attention_partial(
        q, k_seg, v_seg, q_pos, seg_pos, scale=scale, causal=False,
        window=0, mask=seg_mask, block_q=block_q,
        block_k=max(8, k_seg.shape[2]), interpret=interpret)
    return merge_partials([hist, seg])
