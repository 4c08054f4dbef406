"""ModelRunner: executes one model (target LLM or drafter SSM) over a
slot-based, device-resident batched cache with jit-compiled,
shape-bucketed step functions.

Slot model (continuous batching): the runner preallocates ONE cache
pytree whose batch axis is a pool of request *slots*. Requests are
admitted into free slots at prefill and evicted on completion; every
batched step passes its active slot indices into the model's write path
(`model.slot_decode_step` / `slot_verify_chunk` / `slot_extend` →
`apply(..., slot_idx=...)`), which scatters only the new tokens' rows
into the resident cache in place (paged-attention style) and gathers
only the active rows for attention/SSM reads — per-step cache byte
traffic scales with the number of new tokens, not bucket x capacity,
and no host-side pytree reassembly (`stack_caches`/`split_cache`)
happens per step. Active-slot counts are padded to buckets to bound
recompiles; padded rows are mapped to a dedicated scratch slot (index 0)
that no request ever owns, so their garbage writes are never read.

Speculative rollback is snapshot-based: drafting gathers a compact
sub-cache once (`speculative_caches`, a device-side copy) and decodes on
it without ever scattering back — discarding the snapshot IS the
rollback (correct for both attention KV and SSM recurrent state).

Paged mode (`ModelRunner(..., paged=True)`, DESIGN.md §2.8): the
attention/MLA KV lives in a fixed page pool instead of reserved
per-slot rows. `PagedSlotCacheManager` keeps a host-side block table
per request and hands every step a `page_view` — admission, eviction
and rollback become block-table operations, memory scales with tokens
actually held, and long prompts are not bounded by `max_len`.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models import model as M
from repro.models import quantize

# ---------------------------------------------------------------------
# Shape-bucket constants — the single source of truth (tests import
# these; do not duplicate the values elsewhere).
#
# Rationale: every distinct (batch rows, token width) pair that reaches
# a jitted step function costs one XLA compile. Both axes are therefore
# snapped to power-of-two buckets: compiles are O(log) in the largest
# shape seen, and the pad rows/columns are masked out (scratch slot /
# token_mask) so bucketing never changes results.
#
# PREFILL_BUCKETS / PREFILL_CHUNK (token-width axis): an arbitrary-length
# prompt streams through `slot_extend` as full PREFILL_CHUNK-sized
# writes plus ONE final chunk padded up to the next bucket with the pad
# masked out (token_mask), so a 7-token prompt is a single masked 8-wide
# write instead of a 4+2+1 bucket decomposition — compile shapes stay
# bounded and the number of forwards is ceil(P / PREFILL_CHUNK).
# Sliding-window configs chunk at RING_MARGIN instead — see
# `prefill_chunk_len` for why a scatter may not span more ring columns.
#
# SLOT_BUCKETS (batch-rows axis): active-batch sizes are snapped up via
# `slot_bucket`; the enumeration just bounds the table — past its last
# entry the clamp continues with the next power of two (one compile per
# doubling, never one per batch size).
PREFILL_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512)
PREFILL_CHUNK = 512
SLOT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

# Speculative snapshots gathered from a paged pool reserve this much
# column slack past each request's length so draft-ahead writes (gamma
# plus assumed-extension chains) never wrap a full-attention snapshot.
# RING_MARGIN-sized for the same reason the ring margin exists: it is
# the largest segment one step may write.
SNAP_SLACK = 128


def prefill_bucket(n: int) -> int:
    """Smallest prefill chunk shape >= n (n <= PREFILL_CHUNK)."""
    for b in PREFILL_BUCKETS:
        if b >= n:
            return b
    return PREFILL_CHUNK


def prefill_chunk_len(cfg: ModelConfig) -> int:
    """Max prefill chunk width for a config. Sliding-window layers cache
    KV in a ring of capacity window + RING_MARGIN; one scatter may only
    span RING_MARGIN positions (real + pad) or its columns wrap onto
    keys still inside some query's window — so windowed configs chunk at
    the margin, full-attention ones at PREFILL_CHUNK."""
    from repro.models.attention import RING_MARGIN
    from repro.models.model import effective_window
    win = effective_window(cfg)
    return min(PREFILL_CHUNK, RING_MARGIN) if win else PREFILL_CHUNK


def slot_bucket(n: int) -> int:
    """Smallest bucket >= n (bounds the number of compiled batch shapes).
    Past the enumerated buckets, clamp to the next power of two — one
    compile per doubling, never one per active-batch size."""
    for b in SLOT_BUCKETS:
        if b >= n:
            return b
    return 1 << (n - 1).bit_length()


def decode_step(params, cfg: ModelConfig, tokens, cache):
    """`M.decode_step` on a speculative snapshot with its greedy pick in
    the same program: the logits over the real vocabulary (rows, V), the
    cache, and each row's argmax token (int32) and its softmax
    probability, in float32. It keeps the model step's name, by which
    a device trace finds the drafters' decode programs."""
    lg, cache, _ = M.decode_step(params, cfg, tokens, cache)
    x = lg[:, 0, : cfg.vocab]
    tok = jnp.argmax(x, -1).astype(jnp.int32)
    conf = jnp.take_along_axis(jax.nn.softmax(x, -1), tok[:, None], -1)[:, 0]
    return x, cache, tok, conf


# Module-level jitted steps with cfg static: every ModelRunner with the
# same (hashable, frozen) ModelConfig shares one compile cache — engines
# are created freely in benchmarks without re-tracing. The slotted cache
# is donated where it is replaced, so XLA updates it in place.
_g_decode = jax.jit(decode_step, static_argnames=("cfg",))
_g_extend_plain = jax.jit(M.extend, static_argnames=("cfg",),
                          donate_argnames=("cache",))
_g_slot_decode = jax.jit(M.slot_decode_step, static_argnames=("cfg",),
                         donate_argnames=("cache",))
_g_slot_extend = jax.jit(M.slot_extend, static_argnames=("cfg",),
                         donate_argnames=("cache",))
_g_slot_verify = jax.jit(M.slot_verify_chunk, static_argnames=("cfg",))
_g_gather = jax.jit(M.gather_slots)
_g_scatter = jax.jit(M.scatter_slots, donate_argnames=("cache",))
_g_gather_paged = jax.jit(M.gather_paged_slots, static_argnames=("cfg",))
_g_reset_slot = jax.jit(M.reset_slot_state, static_argnames=("cfg",),
                        donate_argnames=("cache",))
_g_reset_pages = jax.jit(M.reset_pages, static_argnames=("cfg",),
                         donate_argnames=("cache",))


class SlotCacheManager:
    """Owns the slotted cache: slot admission/eviction/reset and
    capacity growth (doubling — recompiles are O(log max_concurrency)).

    Slot 0 is scratch (padding target); real slots are 1..n_slots.
    """

    SCRATCH = 0

    def __init__(self, cfg: ModelConfig, max_len: int, n_slots: int = 8,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.max_len = max_len
        self.dtype = dtype
        self.n_slots = n_slots
        self.cache = M.init_cache(cfg, n_slots + 1, max_len, dtype=dtype)
        # pristine single-slot cache used to reset a slot on (re)admission:
        # clears stale slot_pos / SSM state left by the previous tenant
        self._empty = M.init_cache(cfg, 1, max_len, dtype=dtype)
        self._free = list(range(n_slots, 0, -1))      # pop() -> slot 1 first
        self.slot_of: Dict[int, int] = {}
        self._idx_cache: Dict[tuple, jnp.ndarray] = {}

    IDX_CACHE_MAX = 512

    # -------------------------------------------------------------- admission
    def admit(self, rid: int) -> int:
        """Assign (or return) `rid`'s slot, growing the pool if full."""
        if rid in self.slot_of:
            return self.slot_of[rid]
        if not self._free:
            self._grow()
        slot = self._free.pop()
        self.slot_of[rid] = slot
        # admission never remaps existing rids, so memoized index arrays
        # for other batches stay valid — streaming arrivals must not evict
        # the hot decode-batch indices
        self.cache = _g_scatter(self.cache, self._empty,
                                jnp.asarray([slot], jnp.int32))
        return slot

    def release(self, rid: int):
        """Free `rid`'s slot and drop stale memoized batch indices."""
        slot = self.slot_of.pop(rid, None)
        if slot is not None:
            self._free.append(slot)
            # only batches that contained the departing rid are stale (its
            # slot may be re-issued to a different request)
            for key in [k for k in self._idx_cache if rid in k]:
                del self._idx_cache[key]

    def _grow(self):
        extra = M.init_cache(self.cfg, self.n_slots, self.max_len,
                             dtype=self.dtype)
        self.cache = M.concat_slots(self.cache, extra)
        self._free.extend(range(2 * self.n_slots, self.n_slots, -1))
        self.n_slots *= 2

    # -------------------------------------------------------------- indexing
    def padded_idx(self, rids: Sequence[int]) -> jnp.ndarray:
        """Bucketed (B_bucket,) slot indices; padding rows -> scratch.

        Memoized per rids tuple (hot decode loops reuse the same batch for
        many steps). Admissions leave the memo intact; evictions drop only
        the entries containing the departing rid; total size is bounded by
        IDX_CACHE_MAX (FIFO eviction of the oldest batches)."""
        key = tuple(rids)
        idx = self._idx_cache.get(key)
        if idx is None:
            while len(self._idx_cache) >= self.IDX_CACHE_MAX:
                self._idx_cache.pop(next(iter(self._idx_cache)))
            lst = [self.slot_of[r] for r in rids]
            lst += [self.SCRATCH] * (slot_bucket(len(lst)) - len(lst))
            idx = self._idx_cache[key] = jnp.asarray(lst, jnp.int32)
        return idx

    def length(self, rid: int) -> int:
        """Committed tokens in `rid`'s slot (device-authoritative)."""
        return int(self.cache["lengths"][self.slot_of[rid]])

    # ------------------------------------------------------------ paged hooks
    # The resident pool reserves full capacity per slot, so the paged
    # protocol (allocate-before-write, block-table views) is a no-op
    # here; ModelRunner calls these unconditionally and passes the
    # returned page_view (None) straight through to the step functions.
    def prepare(self, rids: Sequence[int], write: int,
                read_extra: int = 0) -> Optional[jnp.ndarray]:
        """Allocate pages for the next `write` columns of each rid and
        return the batch page_view (None on the resident pool)."""
        return None

    def advance(self, rid: int, n: int):
        """Advance the host-side length mirror after a committed write
        of `n` real tokens (paged bookkeeping; no-op here)."""

    def snapshot_view(self, rids: Sequence[int]) -> Optional[jnp.ndarray]:
        """Read-only page_view for a speculative snapshot gather (None
        on the resident pool)."""
        return None


class PagedSlotCacheManager(SlotCacheManager):
    """Slot manager over a paged KV pool (DESIGN.md §2.8).

    Attention/MLA KV lives in one fixed pool of `page_size`-token pages
    per sub-layer; each request owns an ordered host-side *block table*
    mapping its logical pages to physical ones. SSM state, cross-attn
    KV and `lengths` stay slot-indexed (they are O(1) per request).

    Protocol: every write site calls `prepare(rids, write=W)` first —
    it allocates any page the next W columns touch and returns the
    bucketed (rows, n_view) page_view — and `advance(rid, n_real)`
    after the write commits. Eviction (`release`) returns the pages to
    the free list and wipes their slot_pos in one batched reset, so
    recycled pages are invisible until rewritten; admission resets only
    the slot-indexed leaves. Rollback needs nothing at all: speculative
    snapshots are gathered *copies* (`gather_paged_slots`), so dropping
    a snapshot can never leak or alias pages.

    Physical pages 0 and 1 are reserved: 0 is SCRATCH (write target for
    padded batch rows — garbage, never read) and 1 is NULL (read filler
    for unmapped view entries — slot_pos stays -1 forever, never
    written, so it masks like any empty slot).

    Windowed (SWA) layers keep their ring semantics: the block table is
    a fixed ring of C/page_size entries (C = window + RING_MARGIN,
    page_size fitted to divide C) allocated on first touch, and the
    view is always the whole ring — write columns pos % C land on the
    same pages as the resident ring, bit-for-bit.
    """

    SCRATCH_PAGE = 0
    NULL_PAGE = 1
    _RESERVED = 2

    def __init__(self, cfg: ModelConfig, max_len: int, n_slots: int = 8,
                 dtype=jnp.float32, page_size: int = 64,
                 pool_pages: int = 0):
        from repro.models.attention import cache_capacity
        self.cfg = cfg
        self.max_len = max_len
        self.dtype = dtype
        self.n_slots = n_slots
        win = M.effective_window(cfg)
        ps = max(1, page_size)
        if win:
            cap = cache_capacity(cfg, max_len, win)
            while cap % ps:        # ring capacity must be whole pages
                ps //= 2
            self.ring_pages = cap // ps
        else:
            self.ring_pages = 0
        self.page_size = ps
        n_pages = pool_pages or (self._RESERVED + 4 * n_slots)
        n_pages = max(n_pages, self._RESERVED + 1)
        self.n_pages = n_pages
        self.cache = M.init_paged_cache(cfg, n_slots + 1, dtype=dtype,
                                        page_size=ps, n_pages=n_pages)
        self._free = list(range(n_slots, 0, -1))      # pop() -> slot 1 first
        self._free_pages = list(range(n_pages - 1, self._RESERVED - 1, -1))
        self.slot_of: Dict[int, int] = {}
        self._idx_cache: Dict[tuple, jnp.ndarray] = {}
        self.tables: Dict[int, List[int]] = {}
        self.host_len: Dict[int, int] = {}

    # -------------------------------------------------------------- admission
    def admit(self, rid: int) -> int:
        """Assign a slot + empty block table; resets only the
        slot-indexed leaves (pages are mapped lazily by `prepare`)."""
        if rid in self.slot_of:
            return self.slot_of[rid]
        if not self._free:
            self._grow()
        slot = self._free.pop()
        self.slot_of[rid] = slot
        self.tables[rid] = [-1] * self.ring_pages if self.ring_pages else []
        self.host_len[rid] = 0
        self.cache = _g_reset_slot(cfg=self.cfg, cache=self.cache,
                                   slot_idx=jnp.asarray([slot], jnp.int32))
        return slot

    def release(self, rid: int):
        """Free the slot, wipe the mapped pages' slot_pos in one
        batched reset, and return them to the free list."""
        pids = [p for p in self.tables.pop(rid, []) if p >= 0]
        self.host_len.pop(rid, None)
        super().release(rid)
        if pids:
            # one batched slot_pos wipe; pad to a power-of-two count with
            # the NULL page (already -1, so the pad is a no-op)
            n = 1 << (len(pids) - 1).bit_length()
            padded = pids + [self.NULL_PAGE] * (n - len(pids))
            self.cache = _g_reset_pages(
                cfg=self.cfg, cache=self.cache,
                page_ids=jnp.asarray(padded, jnp.int32))
            self._free_pages.extend(reversed(pids))

    def _grow(self):
        extra = M.init_paged_cache(self.cfg, self.n_slots, dtype=self.dtype,
                                   page_size=self.page_size, n_pages=2)
        self.cache = M.concat_slots_paged(self.cfg, self.cache, extra)
        self._free.extend(range(2 * self.n_slots, self.n_slots, -1))
        self.n_slots *= 2

    def _grow_pages(self):
        extra = self.n_pages                      # double the pool
        self.cache = M.grow_pages(self.cfg, self.cache, extra)
        self._free_pages = (list(range(self.n_pages + extra - 1,
                                       self.n_pages - 1, -1))
                            + self._free_pages)
        self.n_pages += extra

    def _alloc_page(self) -> int:
        if not self._free_pages:
            self._grow_pages()
        return self._free_pages.pop()

    # -------------------------------------------------------------- paging
    def ensure(self, rid: int, upto: int):
        """Map every page that columns [host_len, upto) touch. Full
        attention grows the table; windowed maps ring entries on first
        touch. Called by `prepare` before any write."""
        tbl = self.tables[rid]
        hl = self.host_len[rid]
        ps = self.page_size
        if upto <= hl:
            return
        if self.ring_pages:
            for lp in range(hl // ps, (upto - 1) // ps + 1):
                r = lp % self.ring_pages
                if tbl[r] < 0:
                    tbl[r] = self._alloc_page()
        else:
            need = (upto + ps - 1) // ps
            while len(tbl) < need:
                tbl.append(self._alloc_page())

    def view(self, rids: Sequence[int], extra: int = 0) -> jnp.ndarray:
        """Bucketed (rows, n_view) block-table view for a batch.

        n_view covers each rid's held tokens plus `extra` columns,
        snapped to a power of two (windowed: always the whole ring).
        Unmapped entries -> NULL page; padded batch rows -> SCRATCH."""
        rows = slot_bucket(max(len(rids), 1))
        ps = self.page_size
        if self.ring_pages:
            nv = self.ring_pages
        else:
            need = 1
            for r in rids:
                need = max(need, -(-(self.host_len[r] + extra) // ps))
            nv = 1 << (need - 1).bit_length()
        out = np.full((rows, nv), self.NULL_PAGE, np.int32)
        for j, r in enumerate(rids):
            for i, p in enumerate(self.tables[r][:nv]):
                if p >= 0:
                    out[j, i] = p
        out[len(rids):, :] = self.SCRATCH_PAGE
        return jnp.asarray(out)

    def prepare(self, rids: Sequence[int], write: int,
                read_extra: int = 0) -> jnp.ndarray:
        """Allocate pages for the next `write` columns of each rid and
        return the page_view covering held + write + read_extra."""
        if write:
            for r in rids:
                self.ensure(r, self.host_len[r] + write)
        return self.view(rids, extra=write + read_extra)

    def advance(self, rid: int, n: int):
        """Record `n` committed tokens (host paging mirror)."""
        self.host_len[rid] += n

    def snapshot_view(self, rids: Sequence[int]) -> jnp.ndarray:
        """View for a snapshot gather with SNAP_SLACK columns of slack so
        draft-ahead writes on the (copied) snapshot never wrap."""
        return self.view(rids, extra=SNAP_SLACK)

    # -------------------------------------------------------------- accounting
    def pages_held(self) -> int:
        """Physical pages currently mapped by live requests."""
        return sum(sum(1 for p in t if p >= 0) for t in self.tables.values())

    def fragmentation(self) -> float:
        """Fraction of held page capacity that is not live tokens —
        internal fragmentation of the tail pages (0.0 = perfectly full)."""
        held = self.pages_held() * self.page_size
        if not held:
            return 0.0
        live = sum(min(self.host_len[r], self.ring_pages * self.page_size
                       if self.ring_pages else self.host_len[r])
                   for r in self.tables)
        return 1.0 - live / held


class ModelRunner:
    """Executes one model over its slot cache with jitted, bucketed steps.

    paged=True swaps the reserved-capacity `SlotCacheManager` for the
    `PagedSlotCacheManager` (page-pool KV, block tables — DESIGN.md
    §2.8); every step then threads the manager's `page_view` into the
    model's read/write path. The two modes produce identical committed
    tokens — the paged path is gated behind `CoSineConfig.paged_pool`.
    """

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512,
                 n_slots: int = 8, paged: bool = False, page_size: int = 64,
                 pool_pages: int = 0):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        # caches hold activations, so they take the model's compute dtype
        cache_dtype = jnp.dtype(cfg.dtype)
        self.paged = paged
        if paged:
            self.slots: SlotCacheManager = PagedSlotCacheManager(
                cfg, max_len, n_slots, cache_dtype,
                page_size=page_size, pool_pages=pool_pages)
        else:
            self.slots = SlotCacheManager(cfg, max_len, n_slots, cache_dtype)
        # routing prior embeddings: dequantized view for weight-only
        # int8 params (the router works in f32 host space either way).
        # Sliced and widened on the host: an f32 copy of a full-vocabulary
        # table on the device would not fit beside a full-width model.
        embed = jax.device_get(params["embed"])
        if quantize.is_quantized(embed):
            self.embed_np = (embed["w8"][: cfg.vocab].astype(np.float32)
                             * embed["scale"][: cfg.vocab])
        else:
            self.embed_np = np.asarray(embed[: cfg.vocab], np.float32)
        # masked slot_extend writes issued by the prefill paths (the
        # burst-admission test asserts batched prefill issues fewer)
        self.n_prefill_writes = 0
        # bytes that decode, pick and the commit's tails copied to the host
        self.readback_bytes = 0
        # (logits, token, probability) of the last `decode_device`, on
        # the device until `pick` fetches them
        self._pick = None

        self._jit_decode = partial(_g_decode, cfg=cfg)
        self._jit_extend_plain = partial(_g_extend_plain, cfg=cfg)
        self._jit_slot_decode = partial(_g_slot_decode, cfg=cfg)
        self._jit_slot_extend = partial(_g_slot_extend, cfg=cfg)
        self._jit_slot_verify = partial(_g_slot_verify, cfg=cfg)
        self._jit_gather_paged = partial(_g_gather_paged, cfg=cfg)

    def _fetch(self, x):
        """Copy a device result (an array or a tuple of them, in one
        transfer) to the host, counted in `readback_bytes`."""
        out = jax.device_get(x)
        self.readback_bytes += sum(np.asarray(a).nbytes
                                   for a in jax.tree.leaves(out))
        return out

    # ----------------------------------------------------------- lifecycle
    def prefill_request(self, rid: int, tokens: np.ndarray):
        """Admit a slot and prefill the request's context; returns
        (last-position logits (V,), mean next-token logprob of the context
        under this model).

        The logprob is the engine's content-based routing prior (paper §5:
        requests are analyzed and matched to suitable drafters before
        inference). Runs in shape buckets (exact coverage — no padded
        garbage reaches SSM states)."""
        self.slots.admit(rid)
        toks = np.asarray(tokens, np.int32)
        if len(toks) == 0:
            # legal for one-behind drafter caches of a single-token prompt:
            # the slot holds the empty context; the first decode() fills it
            return None, 0.0
        sidx = self.slots.padded_idx([rid])
        rows = int(sidx.shape[0])
        chunk_len = prefill_chunk_len(self.cfg)
        logits = None
        ll_sum, ll_n = 0.0, 0
        i = 0
        while i < len(toks):
            n_real = min(chunk_len, len(toks) - i)
            width = min(prefill_bucket(n_real), chunk_len)
            if i + width > self.max_len:
                # a padded tail would spill past the cache capacity and
                # its ring columns could clobber live rows — fall back to
                # an exact-width write (prompt ~ max_len; one-off shape)
                width = n_real
            seg = np.zeros((rows, width), np.int32)
            seg[0, :n_real] = toks[i: i + n_real]
            mask = np.zeros((rows, width), bool)
            mask[0, :n_real] = True            # batch-pad rows stay masked
            pv = self.slots.prepare([rid], write=width)
            logits, self.slots.cache, _ = self._jit_slot_extend(
                self.params, tokens=jnp.asarray(seg), cache=self.slots.cache,
                slot_idx=sidx, token_mask=jnp.asarray(mask), page_view=pv)
            self.n_prefill_writes += 1
            self.slots.advance(rid, n_real)
            # likelihood of the *next* tokens within this chunk
            nxt = toks[i + 1: i + n_real]
            if len(nxt):
                lp = jax.nn.log_softmax(
                    logits[0, : len(nxt), : self.cfg.vocab], -1)
                ll_sum += float(jnp.take_along_axis(
                    lp, jnp.asarray(nxt)[:, None], -1).sum())
                ll_n += len(nxt)
            i += n_real
        mean_ll = ll_sum / max(ll_n, 1)
        # n_real is the final chunk's real-token count after the loop
        return np.asarray(logits[0, n_real - 1, : self.cfg.vocab]), mean_ll

    def prefill_requests(self, reqs: Dict[int, Sequence[int]]
                         ) -> Dict[int, tuple]:
        """Burst admission: prefill several cold requests with ONE masked
        `slot_extend` write — each request is a row, prompts padded to
        the common bucketed width with the pad masked out (the same
        suffix-pad mechanism the chunked single-request path uses per
        row). Prompts longer than one chunk, empty contexts (one-behind
        drafter caches of single-token prompts) and singleton bursts
        fall back to `prefill_request`. Returns {rid: (last-position
        logits, mean next-token logprob)}."""
        out: Dict[int, tuple] = {}
        chunk_len = min(prefill_chunk_len(self.cfg), self.max_len)
        batch: Dict[int, np.ndarray] = {}
        for rid, tokens in reqs.items():
            toks = np.asarray(tokens, np.int32)
            if 0 < len(toks) <= chunk_len:
                batch[rid] = toks
            else:
                out[rid] = self.prefill_request(rid, toks)
        if len(batch) == 1:
            rid, toks = next(iter(batch.items()))
            out[rid] = self.prefill_request(rid, toks)
            return out
        if not batch:
            return out
        for rid in batch:
            self.slots.admit(rid)
        rids = list(batch)
        sidx = self.slots.padded_idx(rids)
        rows = int(sidx.shape[0])
        maxn = max(len(t) for t in batch.values())
        width = min(prefill_bucket(maxn), chunk_len)
        seg = np.zeros((rows, width), np.int32)
        mask = np.zeros((rows, width), bool)
        for j, rid in enumerate(rids):
            t = batch[rid]
            seg[j, : len(t)] = t
            mask[j, : len(t)] = True
        pv = self.slots.prepare(rids, write=width)
        logits, self.slots.cache, _ = self._jit_slot_extend(
            self.params, tokens=jnp.asarray(seg), cache=self.slots.cache,
            slot_idx=sidx, token_mask=jnp.asarray(mask), page_view=pv)
        self.n_prefill_writes += 1
        for rid in rids:
            self.slots.advance(rid, len(batch[rid]))
        lp = np.asarray(jax.nn.log_softmax(
            logits[:, :, : self.cfg.vocab], -1))
        for j, rid in enumerate(rids):
            t = batch[rid]
            n = len(t)
            nxt = t[1:]
            ll = (float(np.take_along_axis(
                lp[j, : n - 1], nxt[:, None], -1).sum()) / (n - 1)
                if n > 1 else 0.0)
            out[rid] = (np.asarray(logits[j, n - 1, : self.cfg.vocab]), ll)
        return out

    def drop(self, rid: int):
        """Evict `rid`: slot (and pages, when paged) return to the pool."""
        self.slots.release(rid)

    # ----------------------------------------------------------- batched ops
    def speculative_caches(self, rids: Sequence[int]):
        """Device-side snapshot of the requests' slots as one compact
        batched cache (bucketed batch). Decoding on it never touches the
        slotted cache — discarding it is the speculative rollback. On a
        paged pool this gathers only the mapped pages (plus SNAP_SLACK
        columns of write headroom) into a plain stacked cache, so the
        snapshot copies tokens actually held, not reserved capacity."""
        idx = self.slots.padded_idx(rids)
        pv = self.slots.snapshot_view(rids)
        if pv is None:
            return _g_gather(self.slots.cache, idx)
        return self._jit_gather_paged(cache=self.slots.cache, slot_idx=idx,
                                      page_view=pv)

    def extend_snapshot(self, caches: dict, tokens: np.ndarray):
        """Teacher-force `tokens` (B, T) into a speculative snapshot
        (optimistic draft-ahead warm-up: replays an assumed context
        extension so chaining can continue past it). Exact time shapes
        (no padding along T — SSM-state safe); padded batch rows receive
        garbage that is never read. Returns (logits (rows, T, padded
        vocab), caches), both left on the device."""
        rows = int(caches["lengths"].shape[0])
        lg, caches, _ = self._jit_extend_plain(
            self.params,
            tokens=jnp.asarray(self._pad_rows(np.asarray(tokens, np.int32),
                                              rows)),
            cache=caches)
        return lg, caches

    def _pad_rows(self, a: np.ndarray, rows: int) -> np.ndarray:
        if a.shape[0] == rows:
            return a
        pad = np.zeros((rows - a.shape[0],) + a.shape[1:], a.dtype)
        return np.concatenate([a, pad], axis=0)

    def _snapshot_step(self, tokens: np.ndarray, caches: dict):
        """The `decode_step` program on a snapshot, tokens (B,) padded to
        its rows: (logits (rows, V), caches, token, probability)."""
        rows = int(caches["lengths"].shape[0])
        toks = self._pad_rows(np.asarray(tokens, np.int32), rows)
        return self._jit_decode(self.params, tokens=jnp.asarray(toks[:, None]),
                                cache=caches)

    def decode_device(self, tokens: np.ndarray, caches: dict):
        """One decode step on a speculative snapshot, tokens (B,), with
        its result left on the device: (logits (rows, V) float32, caches);
        rows past B are padding. The same program picks each row's greedy
        token, which `pick` fetches."""
        lg, caches, tok, conf = self._snapshot_step(tokens, caches)
        self._pick = (lg, tok, conf)
        return lg, caches

    def pick(self, logits) -> Tuple[np.ndarray, np.ndarray]:
        """Each row's greedy token (int32) and its softmax probability
        (float32), host arrays (rows,). For the logits `decode_device`
        last returned this is the pick its program computed, fetched in
        one readback of rows x 8 bytes; any other logits are copied and
        picked on the host."""
        held, self._pick = self._pick, None
        if held is not None and held[0] is logits:
            return self._fetch(held[1:])
        x = self._fetch(logits).astype(np.float32)
        tok = np.argmax(x, -1).astype(np.int32)
        e = np.exp(x - x.max(-1, keepdims=True))
        return tok, np.take_along_axis(e, tok[:, None], -1)[:, 0] / e.sum(-1)

    def decode(self, rids: Sequence[int], tokens: np.ndarray,
               caches: Optional[dict] = None):
        """One decode step. tokens: (B,). Returns logits (B, V) and, when
        `caches` (a speculative snapshot) is passed, its updated copy;
        otherwise the slotted cache is updated in place and None returned."""
        B = len(rids)
        toks = np.asarray(tokens, np.int32)
        if caches is not None:
            lg, new_cache, _, _ = self._snapshot_step(toks, caches)
            return self._fetch(lg)[:B], new_cache
        sidx = self.slots.padded_idx(rids)
        pv = self.slots.prepare(rids, write=1)
        lg, self.slots.cache, _ = self._jit_slot_decode(
            self.params,
            tokens=jnp.asarray(self._pad_rows(toks, sidx.shape[0]))[:, None],
            cache=self.slots.cache, slot_idx=sidx, page_view=pv)
        for r in rids:
            self.slots.advance(r, 1)
        return self._fetch(lg[:B, 0, : self.cfg.vocab]), None

    def verify_device(self, rids: Sequence[int], tokens: np.ndarray,
                      rel_pos: np.ndarray, seg_mask: np.ndarray):
        """Tree/chain verification forward, result left on device (rows
        x Gmax x padded vocab) — the async backend's worker dispatches
        this and defers the host transfer (`device_get`) until the
        acceptance walk actually consumes the logits."""
        B, G = tokens.shape
        sidx = self.slots.padded_idx(rids)
        rows = int(sidx.shape[0])
        mask = np.asarray(seg_mask, bool)
        if rows != B:
            # padded (scratch) rows verify a lower-triangular dummy segment
            mask = np.concatenate(
                [mask, np.broadcast_to(np.tril(np.ones((G, G), bool)),
                                       (rows - B, G, G))], axis=0)
        pv = self.slots.prepare(rids, write=0)
        return self._jit_slot_verify(
            self.params,
            tokens=jnp.asarray(self._pad_rows(np.asarray(tokens, np.int32),
                                              rows)),
            cache=self.slots.cache, slot_idx=sidx,
            rel_pos=jnp.asarray(self._pad_rows(np.asarray(rel_pos, np.int32),
                                               rows)),
            seg_mask=jnp.asarray(mask), page_view=pv)

    def verify(self, rids: Sequence[int], tokens: np.ndarray,
               rel_pos: np.ndarray, seg_mask: np.ndarray) -> np.ndarray:
        """Tree/chain verification (no cache commit).

        tokens: (B, Gmax); rel_pos: (B, Gmax) node depths; seg_mask
        (B, Gmax, Gmax) ancestor mask. Returns logits (B, Gmax, V)."""
        B = tokens.shape[0]
        lg = self.verify_device(rids, tokens, rel_pos, seg_mask)
        return np.asarray(lg[:B, :, : self.cfg.vocab])

    def extend_committed_device(self, rid_tokens: Dict[int, List[int]]
                                ) -> List[Tuple[List[int], jax.Array]]:
        """Commit accepted tokens per request into the slotted cache.
        Groups by token-count so shapes stay exact (SSM-state safe);
        returns each group's rids and its logits (rows, n, padded vocab),
        left on the device."""
        out: List[Tuple[List[int], jax.Array]] = []
        by_len: Dict[int, List[int]] = {}
        for rid, toks in rid_tokens.items():
            by_len.setdefault(len(toks), []).append(rid)
        for n, rids in by_len.items():
            if n == 0:
                continue
            sidx = self.slots.padded_idx(rids)
            toks = np.asarray([rid_tokens[r] for r in rids], np.int32)
            pv = self.slots.prepare(rids, write=n)
            lg, self.slots.cache, _ = self._jit_slot_extend(
                self.params,
                tokens=jnp.asarray(self._pad_rows(toks, int(sidx.shape[0]))),
                cache=self.slots.cache, slot_idx=sidx, page_view=pv)
            for r in rids:
                self.slots.advance(r, n)
            out.append((rids, lg))
        return out

    def extend_committed(self, rid_tokens: Dict[int, List[int]]) -> Dict[int, np.ndarray]:
        """`extend_committed_device`, returning each request's post-commit
        tail logits (V,) on the host."""
        out: Dict[int, np.ndarray] = {}
        for rids, lg in self.extend_committed_device(rid_tokens):
            for i, r in enumerate(rids):
                out[r] = self._fetch(lg[i, -1, : self.cfg.vocab])
        return out

    def length(self, rid: int) -> int:
        """Committed tokens for `rid`."""
        return self.slots.length(rid)
