"""Composable model assembly for all assigned architectures.

A model is described by `ModelConfig`; layers are grouped into *stages*
(maximal repeated patterns of per-layer specs) and each stage is executed
with `jax.lax.scan` over stacked parameters, so 61-layer models compile as
small HLO. One `apply()` serves train/score, prefill, decode and
speculative verification (chain or tree) — mode is determined by
(cache, seg_mask, write).

Params and caches are plain pytrees (nested dicts/tuples of jnp arrays).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import quantize
from repro.models import ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, embed_init,
                                 mlp_params, norm_params)
from repro.models.moe import apply_moe, moe_params


# ====================================================== layer plan

@dataclass(frozen=True)
class LayerSpec:
    mixer: str          # "attn" | "mla" | "ssm"
    cross: bool         # has a cross-attention sub-block
    ffn: str            # "dense" | "moe" | "none"


def _spec_for(cfg: ModelConfig, idx: int) -> LayerSpec:
    kind = cfg.layer_kind(idx)
    if kind == "ssm":
        mixer = "ssm"
    elif cfg.attention == "mla":
        mixer = "mla"
    else:
        mixer = "attn"
    if cfg.family == "ssm":
        ffn = "none" if cfg.d_ff == 0 else "dense"
    elif cfg.is_moe_layer(idx):
        ffn = "moe"
    else:
        ffn = "dense"
    cross = cfg.is_cross_layer(idx) or cfg.is_encdec
    return LayerSpec(mixer=mixer, cross=cross, ffn=ffn)


def _compress(specs: list) -> list:
    """Greedy max-coverage run-length stage compression.

    Returns [(pattern tuple, repeats), ...] with sum(len(p)*r) == len(specs).
    """
    stages = []
    i = 0
    n = len(specs)
    while i < n:
        best_p, best_k = 1, 1
        for p in range(1, (n - i) // 2 + 1):
            k = 1
            while specs[i + k * p: i + (k + 1) * p] == specs[i: i + p]:
                k += 1
            if k > 1 and (p * k > best_p * best_k
                          or (p * k == best_p * best_k and p < best_p)):
                best_p, best_k = p, k
        if best_k == 1:  # no repetition: take the longest non-repeating run
            best_p = n - i
        stages.append((tuple(specs[i: i + best_p]), best_k))
        i += best_p * best_k
    return stages


def layer_plan(cfg: ModelConfig) -> list:
    return _compress([_spec_for(cfg, i) for i in range(cfg.n_layers)])


def effective_window(cfg: ModelConfig) -> int:
    if cfg.attention == "swa" and cfg.sliding_window:
        return cfg.sliding_window
    if cfg.long_context == "swa":
        return cfg.long_context_window
    return 0


# ====================================================== params

def _init_sublayer(key, spec: LayerSpec, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p = {"ln1": norm_params(cfg, cfg.d_model)}
    if spec.mixer == "attn":
        p["mixer"] = attn.gqa_params(ks[0], cfg)
    elif spec.mixer == "mla":
        p["mixer"] = attn.mla_params(ks[0], cfg)
    else:
        p["mixer"] = ssm_mod.ssm_params(ks[0], cfg)
    if spec.cross:
        p["ln_cross"] = norm_params(cfg, cfg.d_model)
        p["cross"] = attn.gqa_params(ks[1], cfg)
    if spec.ffn != "none":
        p["ln2"] = norm_params(cfg, cfg.d_model)
        if spec.ffn == "moe":
            p["ffn"] = moe_params(ks[2], cfg, cfg.moe)
        else:
            p["ffn"] = mlp_params(ks[2], cfg, cfg.d_model, cfg.d_ff)
    return p


def _as_param_dtype(tree, cfg: ModelConfig):
    """Cast floating leaves to the serving dtype `cfg.dtype`."""
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def _init_stage(key, pattern, repeats, cfg: ModelConfig):
    # cast inside the vmapped init: run eagerly, each stacked leaf is
    # converted as soon as it is drawn, so the whole stage never exists
    # in f32 at once (a 40-layer 4B stage would be ~12 GiB)
    def init_one(k):
        kk = jax.random.split(k, len(pattern))
        return _as_param_dtype(tuple(_init_sublayer(kk[j], pattern[j], cfg)
                                     for j in range(len(pattern))), cfg)
    return jax.vmap(init_one)(jax.random.split(key, repeats))


def _init_encoder(key, cfg: ModelConfig):
    """Whisper-style bidirectional encoder (frontend embeds in, states out)."""
    spec = LayerSpec(mixer="attn", cross=False, ffn="dense")
    k1, k2 = jax.random.split(key)
    return {
        "stage": _init_stage(k1, (spec,), cfg.encoder_layers, cfg),
        "final_norm": norm_params(cfg, cfg.d_model),
        "pos": embed_init(k2, (max(cfg.encoder_seq, 1), cfg.d_model)),
    }


def init_params(key, cfg: ModelConfig):
    """Seeded random weights in `cfg.dtype` (the serving dtype)."""
    ks = jax.random.split(key, 8)
    plan = layer_plan(cfg)
    params = {
        "embed": embed_init(ks[0], (cfg.padded_vocab, cfg.d_model)),
        "stages": [
            _init_stage(ks[1 + i % 4], pattern, reps, cfg)
            for i, (pattern, reps) in enumerate(plan)
        ],
        "final_norm": norm_params(cfg, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(ks[5], (cfg.d_model, cfg.padded_vocab))
    if cfg.pos_embed == "learned":
        params["pos"] = embed_init(ks[6], (cfg.max_position, cfg.d_model))
    if cfg.is_encdec:
        params["encoder"] = _init_encoder(ks[7], cfg)
    if cfg.mtp:
        km = jax.random.split(ks[4], 3)
        spec = LayerSpec(mixer="mla" if cfg.attention == "mla" else "attn",
                         cross=False, ffn="dense")
        params["mtp"] = {
            "proj": embed_init(km[0], (2 * cfg.d_model, cfg.d_model)),
            "norm_h": norm_params(cfg, cfg.d_model),
            "norm_e": norm_params(cfg, cfg.d_model),
            "layer": _init_sublayer(km[1], spec, cfg),
        }
    return _as_param_dtype(params, cfg)


# ====================================================== caches

def _reject_mla_int8(cfg: ModelConfig):
    """MLA caches store the *latent* KV (compressed projections consumed
    by einsum up-projections), which has no per-head int8 layout yet —
    fail at construction rather than silently keeping a bf16 pool."""
    if cfg.kv_dtype == "int8":
        raise ValueError(
            "kv_dtype='int8' is not supported with attention='mla': the "
            "latent KV cache has no quantized layout (use GQA, or "
            "kv_dtype='bf16' for MLA models)")


def _sublayer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                    max_len: int, dtype, cross_len: int):
    window = 0 if spec.mixer == "ssm" else effective_window(cfg)
    c = {}
    if spec.mixer == "attn":
        cap = attn.cache_capacity(cfg, max_len, window)
        hd = cfg.resolved_head_dim
        c["self"] = attn.make_kv_cache(batch, cap, cfg.n_kv_heads, hd, hd,
                                       dtype, quantized=cfg.kv_dtype == "int8")
    elif spec.mixer == "mla":
        _reject_mla_int8(cfg)
        cap = attn.cache_capacity(cfg, max_len, window)
        c["self"] = attn.make_mla_cache(batch, cap, cfg, dtype)
    else:
        c["self"] = ssm_mod.make_ssm_state(batch, cfg)
    if spec.cross:
        hd = cfg.resolved_head_dim
        c["cross"] = attn.make_kv_cache(batch, max(cross_len, 1),
                                        cfg.n_kv_heads, hd, hd, dtype)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16):
    """Decode/prefill cache pytree mirroring the stage structure."""
    cross_len = cfg.n_frontend_tokens if not cfg.is_encdec else cfg.encoder_seq
    stages = []
    for pattern, reps in layer_plan(cfg):
        per = []
        for j in range(len(pattern)):
            c = _sublayer_cache(pattern[j], cfg, batch, max_len, dtype,
                                cross_len)
            per.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (reps,) + x.shape), c))
        stages.append(tuple(per))
    return {"stages": stages, "lengths": jnp.zeros((batch,), jnp.int32)}


def stack_caches(caches):
    """Concatenate per-request caches (batch axis 1 inside stages, axis 0
    for lengths) into one batched cache."""
    stages = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                          *[c["stages"] for c in caches])
    lengths = jnp.concatenate([c["lengths"] for c in caches], axis=0)
    return {"stages": stages, "lengths": lengths}


def split_cache(cache, n):
    """Inverse of stack_caches: n per-request caches."""
    return [{"stages": jax.tree.map(lambda x: x[:, i: i + 1], cache["stages"]),
             "lengths": cache["lengths"][i: i + 1]} for i in range(n)]


# ====================================================== slotted caches
#
# Continuous batching without host pytree traffic: one device-resident
# cache whose batch axis is a pool of request *slots*. Resident steps
# thread slot_idx all the way into the mixer write path (apply(...,
# slot_idx=...)): new KV rows / recurrent states are scattered in place
# into the active slots only (paged-attention style), and reads gather
# just the active rows — per-step cache byte traffic scales with the
# number of new tokens, not bucket x capacity. gather_slots survives for
# speculative snapshots (decode-and-discard rollback) and scatter_slots
# for slot resets on admission. Inside "stages" the slot (batch) axis is
# 1 (axis 0 is the scan-repeat axis); "lengths" carries it on axis 0 —
# the same layout stack_caches produces.

def gather_slots(cache, slot_idx):
    """Device-side gather of a compact sub-cache. slot_idx: (B,) int32.

    The result is structurally identical to `stack_caches` over those
    slots, so every existing step function runs on it unchanged."""
    stages = jax.tree.map(lambda x: jnp.take(x, slot_idx, axis=1),
                          cache["stages"])
    return {"stages": stages,
            "lengths": jnp.take(cache["lengths"], slot_idx, axis=0)}


def scatter_slots(cache, sub, slot_idx):
    """Inverse of gather_slots: write sub-cache rows back into their
    slots. Rows with duplicate indices (scratch-slot padding) resolve
    arbitrarily — only ever used for slots no request owns."""
    stages = jax.tree.map(lambda full, part: full.at[:, slot_idx].set(part),
                          cache["stages"], sub["stages"])
    lengths = cache["lengths"].at[slot_idx].set(sub["lengths"])
    return {"stages": stages, "lengths": lengths}


def concat_slots(cache, extra):
    """Append `extra`'s slots after `cache`'s (capacity growth)."""
    stages = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1),
                          cache["stages"], extra["stages"])
    lengths = jnp.concatenate([cache["lengths"], extra["lengths"]], axis=0)
    return {"stages": stages, "lengths": lengths}


def slot_decode_step(params, cfg: ModelConfig, tokens, cache, slot_idx,
                     frontend=None, page_view=None):
    """One decode step resident in the slotted cache. tokens: (B, 1);
    slot_idx: (B,). Writes land in place: only the new token's row of
    each active slot is touched. Rows mapped to the scratch slot are
    compute padding — their writes land in scratch and are never read.
    page_view: block-table view when the pool is paged (DESIGN.md §2.8)."""
    positions = jnp.take(cache["lengths"], slot_idx)[:, None]
    return apply(params, cfg, tokens, positions, cache=cache,
                 frontend=frontend, write=True, slot_idx=slot_idx,
                 page_view=page_view)


def slot_extend(params, cfg: ModelConfig, tokens, cache, slot_idx,
                frontend=None, token_mask=None, page_view=None):
    """Commit a (B, G) chain of accepted tokens into the slotted cache —
    in place: G rows per active slot, never the full sub-cache. frontend
    (modality embeddings) refreshes cross-attention rows for the active
    slots (prefill).

    token_mask: optional (B, G) bool — True for real tokens, False for a
    *suffix* of shape padding (chunked prefill's pad-and-mask final
    chunk). Masked tokens advance nothing: their KV rows are written
    with slot_pos = -1 (invisible to every read, and re-occupied by the
    next real tokens at those positions), SSM state/conv ignore them,
    and `lengths` advances by the real-token count only."""
    G = tokens.shape[1]
    positions = (jnp.take(cache["lengths"], slot_idx)[:, None]
                 + jnp.arange(G, dtype=jnp.int32))
    return apply(params, cfg, tokens, positions, cache=cache,
                 frontend=frontend, write=True, slot_idx=slot_idx,
                 token_mask=token_mask, page_view=page_view)


def slot_verify_chunk(params, cfg: ModelConfig, tokens, cache, slot_idx,
                      rel_pos, seg_mask, page_view=None):
    """Tree/chain verification against slot-resident caches (no commit).

    rel_pos: (B, G) node depths relative to each slot's length — absolute
    positions are resolved on device, so no host read of lengths."""
    positions = jnp.take(cache["lengths"], slot_idx)[:, None] + rel_pos
    logits, _, _ = apply(params, cfg, tokens, positions, cache=cache,
                         seg_mask=seg_mask, write=False, slot_idx=slot_idx,
                         page_view=page_view)
    return logits


# ====================================================== paged caches
#
# Paged slot caches (DESIGN.md §2.8): same structure as the slotted
# cache above except that attention/MLA "self" caches are *page pools*
# with leading (reps, n_pages, page_size, ...) instead of per-slot
# reserved rows (reps, pool, capacity, ...). A request owns an ordered
# list of physical pages (its block table, host-side in the manager);
# reads/writes go through a (B, n_view) `page_view` built from the block
# tables. SSM recurrent state, cross-attention caches and `lengths` stay
# slot-indexed — they are O(1) per request already. All helpers below
# take `cfg` (static under jit) because paged-ness is per-sublayer: only
# the layer plan knows which "self" caches are pools.

def _map_subcaches(cfg: ModelConfig, cache, fn):
    """Rebuild the stages list with fn(spec, subcache_dict) per sublayer."""
    stages = []
    for (pattern, _reps), scache in zip(layer_plan(cfg), cache["stages"]):
        stages.append(tuple(fn(pattern[j], scache[j])
                            for j in range(len(pattern))))
    return stages


def init_paged_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16, *,
                     page_size: int = 64, n_pages: int = 16):
    """Paged decode cache: attention/MLA KV in page pools, the rest slotted.

    Unlike `init_cache` there is no per-slot max_len — attention capacity
    is whatever the block tables map, so long contexts are not a special
    case. `batch` sizes only the slot-indexed leaves (SSM state, cross
    KV, lengths).
    """
    cross_len = cfg.n_frontend_tokens if not cfg.is_encdec else cfg.encoder_seq
    stages = []
    for pattern, reps in layer_plan(cfg):
        per = []
        for j in range(len(pattern)):
            spec = pattern[j]
            hd = cfg.resolved_head_dim
            c = {}
            if spec.mixer == "attn":
                c["self"] = attn.make_paged_kv_cache(
                    n_pages, page_size, cfg.n_kv_heads, hd, hd, dtype,
                    quantized=cfg.kv_dtype == "int8")
            elif spec.mixer == "mla":
                _reject_mla_int8(cfg)
                c["self"] = attn.make_paged_mla_cache(n_pages, page_size,
                                                      cfg, dtype)
            else:
                c["self"] = ssm_mod.make_ssm_state(batch, cfg)
            if spec.cross:
                c["cross"] = attn.make_kv_cache(batch, max(cross_len, 1),
                                                cfg.n_kv_heads, hd, hd, dtype)
            per.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (reps,) + x.shape), c))
        stages.append(tuple(per))
    return {"stages": stages, "lengths": jnp.zeros((batch,), jnp.int32)}


def paged_pool_shape(cfg: ModelConfig, cache):
    """(n_pages, page_size) of the paged pools, or None if no attention."""
    for (pattern, _reps), scache in zip(layer_plan(cfg), cache["stages"]):
        for j, spec in enumerate(pattern):
            if spec.mixer in ("attn", "mla"):
                sp = scache[j]["self"]["slot_pos"]
                return sp.shape[1], sp.shape[2]
    return None


def gather_paged_slots(cfg: ModelConfig, cache, slot_idx, page_view):
    """Materialize a plain stacked sub-cache from a paged pool (speculative
    snapshots). The attention views gather only the mapped pages into
    (reps, B, n_view * ps, ...) — structurally identical to gather_slots'
    output with capacity C = n_view * ps, so drafting / rollback /
    extend_snapshot run on it unchanged. Unmapped view entries are NULL
    pages (slot_pos -1 ⇒ masked)."""
    B, nv = page_view.shape

    def gather(spec, c):
        nc = {}
        for key, sub in c.items():
            if key == "self" and spec.mixer in ("attn", "mla"):
                ps = sub["slot_pos"].shape[-1]
                rows = (page_view[:, :, None] * ps
                        + jnp.arange(ps, dtype=page_view.dtype)
                        ).reshape(B, nv * ps)
                nc[key] = {
                    f: jnp.take(
                        v.reshape((v.shape[0], v.shape[1] * v.shape[2])
                                  + v.shape[3:]),
                        rows, axis=1)
                    for f, v in sub.items()}
            else:
                nc[key] = jax.tree.map(
                    lambda v: jnp.take(v, slot_idx, axis=1), sub)
        return nc

    return {"stages": _map_subcaches(cfg, cache, gather),
            "lengths": jnp.take(cache["lengths"], slot_idx, axis=0)}


def reset_pages(cfg: ModelConfig, cache, page_ids):
    """Mark physical pages empty (slot_pos = -1) in every paged pool —
    page free/realloc. K/V payloads are left as garbage; masking is
    always against slot_pos so they are unreadable."""
    def reset(spec, c):
        if spec.mixer not in ("attn", "mla"):
            return c
        nc = dict(c)
        s = dict(c["self"])
        s["slot_pos"] = s["slot_pos"].at[:, page_ids].set(-1)
        nc["self"] = s
        return nc

    return {"stages": _map_subcaches(cfg, cache, reset),
            "lengths": cache["lengths"]}


def reset_slot_state(cfg: ModelConfig, cache, slot_idx):
    """Reset the slot-indexed leaves of a paged cache on (re-)admission:
    SSM state/conv/pos zeroed, cross rows emptied, lengths zeroed. The
    paged pools are untouched — page recycling is `reset_pages`."""
    def reset(spec, c):
        nc = dict(c)
        if spec.mixer == "ssm":
            nc["self"] = {f: v.at[:, slot_idx].set(0)
                          for f, v in c["self"].items()}
        if "cross" in c:
            cr = dict(c["cross"])
            cr["slot_pos"] = cr["slot_pos"].at[:, slot_idx].set(-1)
            nc["cross"] = cr
        return nc

    return {"stages": _map_subcaches(cfg, cache, reset),
            "lengths": cache["lengths"].at[slot_idx].set(0)}


def concat_slots_paged(cfg: ModelConfig, cache, extra):
    """Slot-capacity growth for a paged cache: slot-indexed leaves (SSM,
    cross, lengths) get `extra`'s slots appended; the shared page pools
    keep `cache`'s arrays (pool growth is `grow_pages`)."""
    plan = layer_plan(cfg)
    stages = []
    for (pattern, _reps), sc, se in zip(plan, cache["stages"],
                                        extra["stages"]):
        per = []
        for j in range(len(pattern)):
            spec = pattern[j]
            nc = {}
            for key in sc[j]:
                if key == "self" and spec.mixer in ("attn", "mla"):
                    nc[key] = sc[j][key]
                else:
                    nc[key] = jax.tree.map(
                        lambda a, b: jnp.concatenate([a, b], axis=1),
                        sc[j][key], se[j][key])
            per.append(nc)
        stages.append(tuple(per))
    lengths = jnp.concatenate([cache["lengths"], extra["lengths"]], axis=0)
    return {"stages": stages, "lengths": lengths}


def grow_pages(cfg: ModelConfig, cache, extra_pages: int):
    """Append `extra_pages` empty physical pages to every paged pool."""
    def grow(spec, c):
        if spec.mixer not in ("attn", "mla"):
            return c
        nc = dict(c)
        s = {}
        for f, v in c["self"].items():
            pad = jnp.full((v.shape[0], extra_pages) + v.shape[2:],
                           -1 if f == "slot_pos" else 0, v.dtype)
            s[f] = jnp.concatenate([v, pad], axis=1)
        nc["self"] = s
        return nc

    return {"stages": _map_subcaches(cfg, cache, grow),
            "lengths": cache["lengths"]}


# ====================================================== apply

def _apply_sublayer(spec: LayerSpec, p, cache, x, positions, cfg: ModelConfig,
                    *, seg_mask, write, kv_src, causal=True, slot_idx=None,
                    token_mask=None, page_view=None):
    """Returns (x, new_cache, aux). With slot_idx, `cache` is a resident
    slot pool (batch axis > B): mixers gather the active rows for reads
    and `new_cache` holds sub-sized *write deltas* (new KV rows / fresh
    recurrent states) instead of updated pool arrays — so the enclosing
    lax.scan stacks only new-token-sized outputs, and `apply` scatters
    the deltas into the donated resident cache once, at the top level of
    the jitted program.

    page_view (B, n_view): the attention/MLA "self" caches are paged page
    pools (DESIGN.md §2.8) — reads gather only the mapped pages; SSM
    state and cross-attention stay slot-indexed via slot_idx."""
    aux = jnp.zeros((), jnp.float32)
    window = 0 if spec.mixer == "ssm" else effective_window(cfg)
    h = apply_norm(p["ln1"], x, cfg)
    self_cache = cache.get("self") if cache is not None else None
    if spec.mixer == "attn":
        if causal:
            out, new_self = attn.gqa_attention(
                p["mixer"], cfg, h, positions, cache=self_cache,
                seg_mask=seg_mask, window=window, slot_idx=slot_idx,
                write=write, token_mask=token_mask, page_view=page_view)
        else:  # encoder: bidirectional, no rope
            out, new_self = _bidir_attention(p["mixer"], cfg, h)
    elif spec.mixer == "mla":
        out, new_self = attn.mla_attention(
            p["mixer"], cfg, h, positions, cache=self_cache,
            seg_mask=seg_mask, window=window, slot_idx=slot_idx, write=write,
            token_mask=token_mask, page_view=page_view)
    else:  # ssm
        out, new_self = ssm_mod.ssm_mixer(p["mixer"], cfg, h,
                                          state=self_cache,
                                          slot_idx=slot_idx, write=write,
                                          token_mask=token_mask)
    if not write:
        new_self = self_cache if slot_idx is None else None
    x = (x + out).astype(x.dtype)

    if slot_idx is not None:
        new_cache = {"self": new_self} if cache is not None else None
    else:
        new_cache = dict(cache) if cache is not None else None
        if new_cache is not None:
            new_cache["self"] = new_self if new_self is not None \
                else self_cache

    if spec.cross:
        h = apply_norm(p["ln_cross"], x, cfg)
        cross_cache = cache.get("cross") if cache is not None else None
        use_src = kv_src if (cross_cache is None or kv_src is not None) else None
        out, new_cross = attn.cross_attention(p["cross"], cfg, h,
                                              kv_src=use_src,
                                              cache=cross_cache,
                                              slot_idx=slot_idx, write=write)
        x = (x + out).astype(x.dtype)
        if new_cache is not None:
            new_cache["cross"] = new_cross

    if spec.ffn != "none":
        h = apply_norm(p["ln2"], x, cfg)
        if spec.ffn == "moe":
            out, aux = apply_moe(p["ffn"], h, cfg, cfg.moe)
        else:
            out = apply_mlp(p["ffn"], h, cfg)
        x = (x + out).astype(x.dtype)
    return x, new_cache, aux


def _bidir_attention(p, cfg: ModelConfig, h):
    """Encoder self-attention: bidirectional, no rope (learned pos already added)."""
    B, T, _ = h.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (h @ p["wq"]).reshape(B, T, hq, hd)
    k = (h @ p["wk"]).reshape(B, T, hkv, hd)
    v = (h @ p["wv"]).reshape(B, T, hkv, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(hq, hd)
        k = k + p["bk"].reshape(hkv, hd)
        v = v + p["bv"].reshape(hkv, hd)
    qg = q.reshape(B, T, hkv, hq // hkv, hd)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    out = attn.blocked_attention(qg, k, v, pos, pos, scale=hd ** -0.5,
                                 causal=False)
    return out.reshape(B, T, hq * hd) @ p["wo"], None


def _scatter_stage_delta(scache, deltas, slot_idx, positions,
                         page_view=None):
    """Scatter one stage's stacked write deltas into the resident pool.

    scache: per-sublayer tuple of cache dicts with leading (reps, pool,
    ...); deltas: matching tuple of {"self"/"cross": delta | None} where
    a delta carries leading (reps, B, ...). Runs at the top level of the
    jitted step (outside the scan), so with buffer donation XLA updates
    the pool in place and per-step written bytes scale with the number
    of new tokens. Duplicate scratch rows resolve arbitrarily — scratch
    contents are never read.

    page_view (B, n_view): the attention/MLA "self" pools are paged —
    the write column c = pos % (n_view * ps) is translated through the
    block table to physical row page_view[b, c // ps] * ps + c % ps.
    The manager pre-allocates every page a write can touch, so writes
    never land on the NULL page (padding rows map to the scratch page)."""
    out = []
    for cj, dj in zip(scache, deltas):
        nc = dict(cj)
        for key, pool_c in cj.items():
            d = dj.get(key) if dj is not None else None
            if d is None:
                continue
            if "ssm" in d:          # recurrent state: per-slot replacement
                nc[key] = {f: pool_c[f].at[:, slot_idx].set(d[f])
                           for f in pool_c}
            elif key != "cross" and page_view is not None:
                # paged self-attention pool: block-table translated rows
                ps = pool_c["slot_pos"].shape[-1]
                n_pages = pool_c["slot_pos"].shape[1]
                col = positions % (page_view.shape[1] * ps)
                phys = (jnp.take_along_axis(page_view, col // ps, axis=1) * ps
                        + col % ps)                              # (B, T)
                upd = {}
                for f in pool_c:
                    rest = pool_c[f].shape[3:]
                    flat = pool_c[f].reshape(
                        (pool_c[f].shape[0], n_pages * ps) + rest)
                    upd[f] = flat.at[:, phys].set(d[f]).reshape(
                        pool_c[f].shape)
                nc[key] = upd
            else:                   # attention KV: new-token rows
                C = pool_c["slot_pos"].shape[-1]
                if key == "cross":  # full-row projections, columns 0..S
                    S = d["slot_pos"].shape[-1]
                    scol = jnp.broadcast_to(jnp.arange(S), (len(slot_idx), S))
                else:               # ring placement, as in write_kv
                    scol = positions % C
                nc[key] = _write_token_rows(pool_c, d, slot_idx, scol)
        out.append(nc)
    return tuple(out)


def _write_token_rows(pools, rows, slot_idx, cols):
    """pools[f][:, slot_idx[b], cols[b, t]] = rows[f][:, b, t] for every
    field f and token (b, t), as one in-place dynamic_update_slice per
    token. The equivalent scatter makes the TPU compiler relayout the
    whole pool and back (two copies of a 1.76 GiB K stack for a 4B model,
    which leaves no room for the model beside its cache); these writes
    keep the pool where it is. Later tokens win on duplicate targets,
    which only padding rows (the scratch slot) produce."""
    B, T = cols.shape

    def body(i, ps):
        b, t = i // T, i % T
        out = {}
        for f, p in ps.items():
            r = rows[f]
            tail = (0,) * (r.ndim - 3)
            row = jax.lax.dynamic_slice(r, (0, b, t) + tail,
                                        (r.shape[0], 1, 1) + r.shape[3:])
            out[f] = jax.lax.dynamic_update_slice(
                p, row.astype(p.dtype), (0, slot_idx[b], cols[b, t]) + tail)
        return out

    return jax.lax.fori_loop(0, B * T, body, dict(pools))


def _apply_stage(pattern, sparams, scache, x, positions, cfg: ModelConfig,
                 *, seg_mask, write, kv_src, causal=True, remat=False,
                 slot_idx=None, token_mask=None, page_view=None):
    def body(carry, xs):
        xx = carry
        lp, lc = xs
        aux_tot = jnp.zeros((), jnp.float32)
        new_lc = []
        for j, spec in enumerate(pattern):
            cj = lc[j] if lc is not None else None
            xx, ncj, aux = _apply_sublayer(
                spec, lp[j], cj, xx, positions, cfg,
                seg_mask=seg_mask, write=write, kv_src=kv_src, causal=causal,
                slot_idx=slot_idx, token_mask=token_mask,
                page_view=page_view)
            new_lc.append(ncj)
            aux_tot = aux_tot + aux
        return xx, (tuple(new_lc), aux_tot)

    if remat:
        body = jax.checkpoint(body)
    xs = (sparams, scache)
    x, (new_cache, auxs) = jax.lax.scan(body, x, xs)
    return x, new_cache, auxs.sum()


def _encode(params, cfg: ModelConfig, frontend):
    """Whisper encoder: frontend embeds (B, S, d) -> encoder states."""
    enc = params["encoder"]
    S = frontend.shape[1]
    x = frontend + enc["pos"][:S]
    spec = LayerSpec(mixer="attn", cross=False, ffn="dense")
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), frontend.shape[:2])
    x, _, _ = _apply_stage((spec,), enc["stage"], None, x, pos, cfg,
                           seg_mask=None, write=False, kv_src=None,
                           causal=False)
    return apply_norm(enc["final_norm"], x, cfg)


def _logits(params, cfg: ModelConfig, x):
    # tied_logits/qdot accept both plain f32/bf16 weights and the
    # weight-only-int8 {"w8","scale"} form (models/quantize.py)
    if cfg.tie_embeddings:
        logits = quantize.tied_logits(params["embed"], x).astype(jnp.float32)
    else:
        logits = quantize.qdot(x, params["head"]).astype(jnp.float32)
    if cfg.padded_vocab != cfg.vocab:
        neg = jnp.full((cfg.padded_vocab - cfg.vocab,), -1e30, jnp.float32)
        logits = logits.at[..., cfg.vocab:].set(neg)
    return logits


def apply(params, cfg: ModelConfig, tokens, positions=None, cache=None,
          frontend=None, seg_mask=None, write=True, remat=False,
          return_hidden=False, slot_idx=None, token_mask=None,
          page_view=None):
    """Unified forward.

    tokens:    (B, T) int32
    positions: (B, T) absolute positions (default arange)
    cache:     None (self-contained) or pytree from init_cache
    frontend:  (B, S, d) stub modality embeddings (audio/vlm)
    seg_mask:  (B, T, T) intra-segment mask (tree verification)
    write:     commit new KV/state into the returned cache
    slot_idx:  (B,) int32 — `cache` is a resident slot pool whose batch
               axis exceeds B; row b of tokens lives in pool slot
               slot_idx[b]. Writes touch only the new tokens' rows of the
               active slots (paged-attention-style in-place update);
               reads gather the active rows. The returned cache is the
               full pool.
    token_mask: (B, T) bool — real tokens True, suffix shape-padding
               False (slot path only; chunked prefill's pad-and-mask
               final chunk). Attention sees masked tokens at position -1
               (their KV rows land at the real column slots but with
               slot_pos = -1, so they are invisible and the next real
               tokens at those positions overwrite them); the SSM mixer
               freezes its state/conv across them; `lengths` advances by
               the real-token count only.
    page_view: (B, n_view) int32 — the slot pool's attention/MLA "self"
               caches are *paged* (init_paged_cache, DESIGN.md §2.8):
               entry [b, i] is the physical page holding request b's
               logical page i (NULL for unmapped tail entries). Reads
               gather only the view's pages; write deltas scatter
               through the block table. Requires slot_idx.
    Returns (logits (B,T,Vp) f32, new_cache, aux_loss) [+ hidden if asked].
    """
    B, T = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    if token_mask is not None:
        assert slot_idx is not None, "token_mask requires the slot path"
    if page_view is not None:
        assert slot_idx is not None, "page_view requires the slot path"
    dtype = jnp.dtype(cfg.dtype)
    x = quantize.embed_lookup(params["embed"], tokens, dtype)
    if cfg.pos_embed == "learned":
        x = x + params["pos"][positions].astype(dtype)

    kv_src = None
    if cfg.is_encdec:
        if frontend is not None:
            kv_src = _encode(params, cfg, frontend.astype(dtype))
    elif cfg.cross_attn_period:
        kv_src = frontend.astype(dtype) if frontend is not None else None

    aux_total = jnp.zeros((), jnp.float32)
    new_stages = []
    plan = layer_plan(cfg)
    cache_stages = cache["stages"] if cache is not None else [None] * len(plan)
    for (pattern, reps), sparams, scache in zip(plan, params["stages"],
                                                cache_stages):
        x, ncache, aux = _apply_stage(
            pattern, sparams, scache, x, positions, cfg,
            seg_mask=seg_mask, write=write, kv_src=kv_src, remat=remat,
            slot_idx=slot_idx, token_mask=token_mask, page_view=page_view)
        if slot_idx is not None and cache is not None:
            # resident path: the scan produced write deltas; scatter them
            # into the pool here (top level, donated buffers)
            ncache = (_scatter_stage_delta(scache, ncache, slot_idx,
                                           positions, page_view)
                      if write else scache)
        new_stages.append(ncache)
        aux_total = aux_total + aux

    x = apply_norm(params["final_norm"], x, cfg)
    logits = _logits(params, cfg, x)

    new_cache = None
    if cache is not None:
        new_len = cache["lengths"]
        if write:
            if slot_idx is None:
                new_len = jnp.maximum(new_len, positions[:, -1] + 1)
            else:
                # masked suffix tokens never advance the slot length (the
                # max masked position is the last *real* one; an
                # all-masked row yields -1 and leaves the length as-is)
                last = (positions[:, -1] if token_mask is None
                        else jnp.where(token_mask, positions, -1).max(-1))
                upd = jnp.maximum(jnp.take(new_len, slot_idx), last + 1)
                new_len = new_len.at[slot_idx].set(upd)
        new_cache = {"stages": new_stages, "lengths": new_len}
    if return_hidden:
        return logits, new_cache, aux_total, x
    return logits, new_cache, aux_total


# ====================================================== losses

def lm_loss(params, cfg: ModelConfig, tokens, frontend=None, remat=True):
    """Next-token CE (+ MoE aux + MTP aux when configured)."""
    logits, _, aux, hidden = apply(params, cfg, tokens, frontend=frontend,
                                   remat=remat, return_hidden=True)
    tgt = tokens[:, 1:]
    lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    loss = nll.mean()
    total = loss + 0.001 * aux

    if cfg.mtp:
        total = total + 0.3 * _mtp_loss(params, cfg, tokens, hidden)
    return total, {"lm": loss, "aux": aux}


def _mtp_loss(params, cfg: ModelConfig, tokens, hidden):
    """DeepSeek-V3 depth-1 multi-token prediction: predict t+2 from
    (h_t, emb(x_{t+1})) through one extra transformer layer."""
    mtp = params["mtp"]
    dtype = hidden.dtype
    B, T = tokens.shape
    h = apply_norm(mtp["norm_h"], hidden[:, : T - 1], cfg)
    e = apply_norm(mtp["norm_e"],
                   quantize.embed_lookup(params["embed"], tokens[:, 1:],
                                         dtype), cfg)
    x = jnp.concatenate([h, e], axis=-1) @ mtp["proj"].astype(dtype)
    spec = LayerSpec(mixer="mla" if cfg.attention == "mla" else "attn",
                     cross=False, ffn="dense")
    pos = jnp.broadcast_to(jnp.arange(T - 1, dtype=jnp.int32), (B, T - 1))
    x, _, _ = _apply_sublayer(spec, mtp["layer"], None, x, pos, cfg,
                              seg_mask=None, write=False, kv_src=None)
    x = apply_norm(params["final_norm"], x, cfg)
    logits = _logits(params, cfg, x)
    tgt = tokens[:, 2:]
    lp = jax.nn.log_softmax(logits[:, : T - 2], axis=-1)
    nll = -jnp.take_along_axis(lp, tgt[..., None], axis=-1)[..., 0]
    return nll.mean()


# ====================================================== convenience wrappers

def prefill(params, cfg: ModelConfig, tokens, cache, frontend=None):
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
    return apply(params, cfg, tokens, positions, cache=cache,
                 frontend=frontend, write=True)


def decode_step(params, cfg: ModelConfig, tokens, cache, frontend=None):
    """tokens: (B, 1) next tokens at positions cache['lengths']."""
    positions = cache["lengths"][:, None]
    return apply(params, cfg, tokens, positions, cache=cache,
                 frontend=frontend, write=True)


def verify_chunk(params, cfg: ModelConfig, tokens, cache, positions=None,
                 seg_mask=None, write=False):
    """Score a draft segment (chain or tree) against the cache without
    committing. tokens: (B, G); positions default chain continuation."""
    B, G = tokens.shape
    if positions is None:
        positions = cache["lengths"][:, None] + jnp.arange(G, dtype=jnp.int32)
    if seg_mask is None:
        seg_mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((G, G), bool)), (B, G, G))
    return apply(params, cfg, tokens, positions, cache=cache,
                 seg_mask=seg_mask, write=write)


def extend(params, cfg: ModelConfig, tokens, cache, frontend=None):
    """Commit accepted tokens (chain) into the cache; returns logits too."""
    B, G = tokens.shape
    positions = cache["lengths"][:, None] + jnp.arange(G, dtype=jnp.int32)
    return apply(params, cfg, tokens, positions, cache=cache,
                 frontend=frontend, write=True)
