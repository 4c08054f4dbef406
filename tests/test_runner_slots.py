"""Slot-based runner equivalence: the slot-resident continuous-batching
cache engine (gather -> step -> scatter inside one jitted program) must
produce logits identical to the seed's per-request flow (independent
batch-1 caches) for prefill, decode, verify and extend — across
attention, SSM and hybrid families — including slot reuse after eviction
and slot-pool growth."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_MAX_LEN as MAX_LEN, tiny_model_cfg as _tiny
from repro.config import ModelConfig
from repro.models import model as M
from repro.serving.runner import ModelRunner, SlotCacheManager, slot_bucket

ATOL = 1e-5


class PerRequestReference:
    """The seed cache-ownership model: one batch-1 cache pytree per
    request, stepped independently (what stack_caches/split_cache
    round-trips computed)."""

    def __init__(self, cfg, params):
        self.cfg, self.params = cfg, params
        self.caches = {}

    def prefill(self, rid, toks):
        cache = M.init_cache(self.cfg, 1, MAX_LEN, dtype=jnp.float32)
        lg, cache, _ = M.prefill(self.params, self.cfg,
                                 jnp.asarray(toks, jnp.int32)[None], cache)
        self.caches[rid] = cache
        return np.asarray(lg[0, -1, : self.cfg.vocab])

    def decode(self, rid, tok):
        lg, self.caches[rid], _ = M.decode_step(
            self.params, self.cfg, jnp.asarray([[tok]], jnp.int32),
            self.caches[rid])
        return np.asarray(lg[0, 0, : self.cfg.vocab])

    def verify(self, rid, toks, rel_pos, seg_mask):
        cache = self.caches[rid]
        positions = cache["lengths"][:, None] + jnp.asarray(rel_pos,
                                                            jnp.int32)[None]
        lg, _, _ = M.verify_chunk(
            self.params, self.cfg, jnp.asarray(toks, jnp.int32)[None], cache,
            positions=positions,
            seg_mask=jnp.asarray(seg_mask, bool)[None], write=False)
        return np.asarray(lg[0, :, : self.cfg.vocab])

    def extend(self, rid, toks):
        lg, self.caches[rid], _ = M.extend(
            self.params, self.cfg, jnp.asarray(toks, jnp.int32)[None],
            self.caches[rid])
        return np.asarray(lg[0, -1, : self.cfg.vocab])


@pytest.fixture(params=["attn", "ssm", "hybrid"])
def pair(request):
    cfg = _tiny(request.param)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    # n_slots=2 so the third admission exercises slot-pool growth
    return (ModelRunner(cfg, params, max_len=MAX_LEN, n_slots=2),
            PerRequestReference(cfg, params), cfg)


def test_prefill_decode_verify_extend_match(pair):
    runner, ref, cfg = pair
    rng = np.random.default_rng(0)
    rids = [0, 1, 2]
    for rid in rids:
        toks = rng.integers(0, cfg.vocab, 7 + 3 * rid)
        lg_s, _ = runner.prefill_request(rid, toks)
        np.testing.assert_allclose(lg_s, ref.prefill(rid, toks), atol=ATOL)

    # batched decode (bucket pads 3 -> 4 with scratch rows)
    step = rng.integers(0, cfg.vocab, 3)
    lg_s, _ = runner.decode(rids, step)
    for i, rid in enumerate(rids):
        np.testing.assert_allclose(lg_s[i], ref.decode(rid, step[i]),
                                   atol=ATOL)

    # chain verification (no commit): logits match, caches untouched
    G = 4
    vt = rng.integers(0, cfg.vocab, (3, G))
    rel = np.broadcast_to(np.arange(G, dtype=np.int32), (3, G))
    mask = np.broadcast_to(np.tril(np.ones((G, G), bool)), (3, G, G))
    lg_s = runner.verify(rids, vt, rel, mask)
    for i, rid in enumerate(rids):
        np.testing.assert_allclose(lg_s[i], ref.verify(rid, vt[i], rel[i],
                                                       mask[i]), atol=ATOL)

    # ragged commit: per-request token counts differ (grouped by length)
    commits = {0: [1, 2], 1: [3], 2: [4, 5, 6]}
    tails = runner.extend_committed(commits)
    for rid, toks in commits.items():
        np.testing.assert_allclose(tails[rid], ref.extend(rid, toks),
                                   atol=ATOL)
        assert runner.length(rid) == int(ref.caches[rid]["lengths"][0])


def test_slot_reuse_after_eviction(pair):
    runner, ref, cfg = pair
    rng = np.random.default_rng(1)
    for rid in (0, 1):
        toks = rng.integers(0, cfg.vocab, 8)
        runner.prefill_request(rid, toks)
        ref.prefill(rid, toks)
    evicted_slot = runner.slots.slot_of[1]
    runner.drop(1)

    # the freed slot must be reused and fully reset (no KV/state leakage
    # from the previous tenant)
    toks = rng.integers(0, cfg.vocab, 11)
    lg_s, _ = runner.prefill_request(9, toks)
    assert runner.slots.slot_of[9] == evicted_slot
    np.testing.assert_allclose(lg_s, ref.prefill(9, toks), atol=ATOL)

    # survivors and the new tenant still decode identically
    step = rng.integers(0, cfg.vocab, 2)
    lg_s, _ = runner.decode([0, 9], step)
    np.testing.assert_allclose(lg_s[0], ref.decode(0, step[0]), atol=ATOL)
    np.testing.assert_allclose(lg_s[1], ref.decode(9, step[1]), atol=ATOL)


def test_speculative_snapshot_is_rollback(pair):
    runner, ref, cfg = pair
    rng = np.random.default_rng(2)
    toks = rng.integers(0, cfg.vocab, 9)
    runner.prefill_request(0, toks)
    ref.prefill(0, toks)

    # draft on a snapshot: advances the snapshot, not the slot cache
    snap = runner.speculative_caches([0])
    for t in rng.integers(0, cfg.vocab, 3):
        _, snap = runner.decode([0], np.asarray([t]), caches=snap)
    assert runner.length(0) == len(toks)

    # the slot cache then commits from its pre-draft state
    step = int(rng.integers(0, cfg.vocab))
    lg_s, _ = runner.decode([0], np.asarray([step]))
    np.testing.assert_allclose(lg_s[0], ref.decode(0, step), atol=ATOL)


def test_inplace_write_path_matches_gather_scatter(pair):
    """The resident write path (apply(..., slot_idx=...)) must be
    bit-identical to the legacy gather -> step -> scatter composition:
    same logits, same active-slot cache contents — across attention, SSM
    and hybrid families, including bucket padding to the scratch slot."""
    runner, _, cfg = pair
    params = runner.params
    rng = np.random.default_rng(7)
    rids = [0, 1, 2]
    for rid in rids:                       # third admission grows the pool
        runner.prefill_request(rid, rng.integers(0, cfg.vocab, 6 + rid))
    idx = runner.slots.padded_idx(rids)    # pads 3 -> 4 with scratch
    rows = int(idx.shape[0])
    cache = runner.slots.cache

    def active(c):
        """Cache contents of the active slots only (scratch excluded)."""
        act = jnp.asarray(sorted({int(s) for s in np.asarray(idx)
                                  if s != SlotCacheManager.SCRATCH}))
        stages = jax.tree.map(lambda x: jnp.take(x, act, axis=1),
                              c["stages"])
        return stages, jnp.take(c["lengths"], act)

    def assert_same(ca, cb):
        for a, b in zip(jax.tree.leaves(active(ca)),
                        jax.tree.leaves(active(cb))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # --- decode ---
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (rows, 1)), jnp.int32)
    lg_a, cache_a, _ = M.slot_decode_step(params, cfg, toks, cache, idx)
    sub = M.gather_slots(cache, idx)
    lg_b, sub, _ = M.decode_step(params, cfg, toks, sub)
    cache_b = M.scatter_slots(cache, sub, idx)
    np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))
    assert_same(cache_a, cache_b)

    # --- verify (no commit): logits match, caches untouched ---
    G = 3
    vt = jnp.asarray(rng.integers(0, cfg.vocab, (rows, G)), jnp.int32)
    rel = jnp.broadcast_to(jnp.arange(G, dtype=jnp.int32), (rows, G))
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((G, G), bool)), (rows, G, G))
    lg_a = M.slot_verify_chunk(params, cfg, vt, cache_a, idx, rel, mask)
    sub = M.gather_slots(cache_b, idx)
    lg_b, _, _ = M.verify_chunk(params, cfg, vt, sub,
                                positions=sub["lengths"][:, None] + rel,
                                seg_mask=mask, write=False)
    np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))
    assert_same(cache_a, cache_b)

    # --- extend (speculative commit) ---
    et = jnp.asarray(rng.integers(0, cfg.vocab, (rows, 2)), jnp.int32)
    lg_a, cache_a, _ = M.slot_extend(params, cfg, et, cache_a, idx)
    sub = M.gather_slots(cache_b, idx)
    lg_b, sub, _ = M.extend(params, cfg, et, sub)
    cache_b = M.scatter_slots(cache_b, sub, idx)
    np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))
    assert_same(cache_a, cache_b)

    # --- eviction and slot reuse keep the paths aligned ---
    runner.slots.cache = cache_a
    runner.drop(1)
    runner.prefill_request(9, rng.integers(0, cfg.vocab, 5))
    idx2 = runner.slots.padded_idx([0, 9])
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 1)), jnp.int32)
    lg_a, cache_a2, _ = M.slot_decode_step(params, cfg, toks,
                                           runner.slots.cache, idx2)
    sub = M.gather_slots(runner.slots.cache, idx2)
    lg_b, _, _ = M.decode_step(params, cfg, toks, sub)
    np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))


def test_inplace_cross_attention_matches_gather_scatter():
    """Cross-attention layers (VLM-style frontend) through the resident
    path: prefill-with-frontend writes the projected cross KV rows as a
    delta into the active slots; decode reads them back — both
    bit-identical to the gather/scatter composition."""
    from repro.config import ModelConfig
    cfg = ModelConfig(name="tiny-cross", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab=50, tie_embeddings=True,
                      dtype="float32", cross_attn_period=2,
                      n_frontend_tokens=4)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pool = M.init_cache(cfg, 4, MAX_LEN, dtype=jnp.float32)
    rng = np.random.default_rng(13)
    idx = jnp.asarray([1, 3], jnp.int32)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 6)), jnp.int32)
    fe = jnp.asarray(rng.normal(size=(2, cfg.n_frontend_tokens,
                                      cfg.d_model)) * 0.1, jnp.float32)

    # prefill with frontend: cross KV rows written in place
    lg_a, pool_a, _ = M.slot_extend(params, cfg, toks, pool, idx,
                                    frontend=fe)
    sub = M.gather_slots(pool, idx)
    lg_b, sub, _ = M.extend(params, cfg, toks, sub, frontend=fe)
    pool_b = M.scatter_slots(pool, sub, idx)
    np.testing.assert_array_equal(np.asarray(lg_a), np.asarray(lg_b))

    # decode without frontend: reads the slot-resident cross cache
    t2 = jnp.asarray(rng.integers(0, cfg.vocab, (2, 1)), jnp.int32)
    lg_a2, _, _ = M.slot_decode_step(params, cfg, t2, pool_a, idx)
    sub = M.gather_slots(pool_b, idx)
    lg_b2, _, _ = M.decode_step(params, cfg, t2, sub)
    np.testing.assert_array_equal(np.asarray(lg_a2), np.asarray(lg_b2))


def test_speculative_snapshot_rollback_after_inplace_steps(pair):
    """Snapshots taken from a cache advanced by in-place writes must
    still be pure copies: drafting on them never leaks into the resident
    cache, and discarding them is a complete rollback."""
    runner, ref, cfg = pair
    rng = np.random.default_rng(11)
    toks = rng.integers(0, cfg.vocab, 7)
    runner.prefill_request(0, toks)
    ref.prefill(0, toks)
    # advance the resident cache in place, then snapshot
    step = int(rng.integers(0, cfg.vocab))
    runner.decode([0], np.asarray([step]))
    ref.decode(0, step)
    snap = runner.speculative_caches([0])
    for t in rng.integers(0, cfg.vocab, 3):
        _, snap = runner.decode([0], np.asarray([t]), caches=snap)
    assert runner.length(0) == len(toks) + 1
    nxt = int(rng.integers(0, cfg.vocab))
    lg, _ = runner.decode([0], np.asarray([nxt]))
    np.testing.assert_allclose(lg[0], ref.decode(0, nxt), atol=ATOL)


def test_short_prompt_prefill_single_padded_chunk():
    """A 7-token prompt must prefill as ONE pad-and-mask slot_extend of
    bucket width 8 (chunked write-through, no 4+2+1 bucket loop) and the
    slot length must count only the real tokens."""
    cfg = _tiny("attn")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    runner = ModelRunner(cfg, params, max_len=MAX_LEN)
    calls = []
    orig_e = runner._jit_slot_extend
    runner._jit_slot_extend = lambda *a, **k: (
        calls.append(int(k["tokens"].shape[1])) or orig_e(*a, **k))
    rng = np.random.default_rng(3)
    toks = rng.integers(0, cfg.vocab, 7)
    lg, _ = runner.prefill_request(0, toks)
    assert calls == [8]
    ref = PerRequestReference(cfg, params)
    np.testing.assert_allclose(lg, ref.prefill(0, toks), atol=ATOL)
    assert runner.length(0) == 7


def _tiny_exotic(kind):
    """MLA / sliding-window tiny variants: the pad-and-mask write path
    must hold for the latent cache and the ring cache too."""
    from repro.config import MLAConfig
    common = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=50, tie_embeddings=True,
                  dtype="float32")
    if kind == "mla":
        return ModelConfig(name="tiny-mla", family="dense", attention="mla",
                           mla=MLAConfig(q_lora_rank=32, kv_lora_rank=32,
                                         qk_nope_head_dim=16,
                                         qk_rope_head_dim=8, v_head_dim=16),
                           **common)
    return ModelConfig(name="tiny-swa", family="dense", attention="swa",
                       sliding_window=16, **common)


@pytest.mark.parametrize("kind", ["mla", "swa"])
def test_padded_chunk_prefill_exotic_attention(kind):
    cfg = _tiny_exotic(kind)
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    runner = ModelRunner(cfg, params, max_len=MAX_LEN)
    ref = PerRequestReference(cfg, params)
    rng = np.random.default_rng(13)
    toks = rng.integers(0, cfg.vocab, 13)        # pads 13 -> 16
    lg, _ = runner.prefill_request(0, toks)
    np.testing.assert_allclose(lg, ref.prefill(0, toks), atol=ATOL)
    for t in rng.integers(0, cfg.vocab, 3):
        lg, _ = runner.decode([0], np.asarray([t]))
        np.testing.assert_allclose(lg[0], ref.decode(0, int(t)), atol=ATOL)


def test_padded_chunk_prefill_swa_prompt_past_ring_capacity():
    """A windowed config chunks prefill at RING_MARGIN: a prompt longer
    than the ring capacity (window + margin) must still be exact — a
    wider padded chunk would scatter pad columns onto keys still inside
    some query's window (regression: 300-token prompt, window 16)."""
    import jax.numpy as jnp
    cfg = _tiny_exotic("swa")                    # window 16, capacity 144
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    runner = ModelRunner(cfg, params, max_len=512)
    rng = np.random.default_rng(4)
    toks = rng.integers(0, cfg.vocab, 300)
    lg, _ = runner.prefill_request(0, toks)
    cache = M.init_cache(cfg, 1, 512, dtype=jnp.float32)
    rlg, cache, _ = M.prefill(params, cfg, jnp.asarray(toks)[None], cache)
    np.testing.assert_allclose(lg, np.asarray(rlg[0, -1, :cfg.vocab]),
                               atol=ATOL)
    for t in rng.integers(0, cfg.vocab, 3):
        dl, _ = runner.decode([0], np.asarray([int(t)]))
        rl, cache, _ = M.decode_step(params, cfg, jnp.asarray([[int(t)]]),
                                     cache)
        np.testing.assert_allclose(dl[0], np.asarray(rl[0, 0, :cfg.vocab]),
                                   atol=ATOL)


@pytest.mark.parametrize("kind", ["attn", "ssm", "hybrid"])
@pytest.mark.parametrize("n", [1, 5, 8, 13])
def test_padded_chunk_prefill_matches_reference(kind, n):
    """Pad-and-mask prefill must be invisible: logits at the last real
    position and every subsequent decode step match the per-request
    reference exactly for attention KV, SSM recurrent/conv state and the
    hybrid mix (the masked tail writes nothing a read can see)."""
    cfg = _tiny(kind)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    runner = ModelRunner(cfg, params, max_len=MAX_LEN)
    ref = PerRequestReference(cfg, params)
    rng = np.random.default_rng(7 + n)
    toks = rng.integers(0, cfg.vocab, n)
    lg, _ = runner.prefill_request(0, toks)
    np.testing.assert_allclose(lg, ref.prefill(0, toks), atol=ATOL)
    assert runner.length(0) == n
    # decoding after a masked prefill keeps matching: the pad rows were
    # never read and the next tokens overwrite their columns
    for t in rng.integers(0, cfg.vocab, 4):
        lg, _ = runner.decode([0], np.asarray([t]))
        np.testing.assert_allclose(lg[0], ref.decode(0, int(t)), atol=ATOL)
    assert runner.length(0) == n + 4


def test_slot_bucket_clamps_to_pow2():
    assert slot_bucket(1) == 1
    assert slot_bucket(3) == 4
    assert slot_bucket(256) == 256
    # past the enumerated buckets: next power of two, not raw n
    assert slot_bucket(257) == 512
    assert slot_bucket(300) == 512
    assert slot_bucket(512) == 512
    assert slot_bucket(513) == 1024


def test_slot_pool_growth_and_buckets():
    cfg = _tiny("attn")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mgr = SlotCacheManager(cfg, MAX_LEN, n_slots=2)
    slots = [mgr.admit(r) for r in range(5)]        # forces two doublings
    assert len(set(slots)) == 5
    assert SlotCacheManager.SCRATCH not in slots
    assert mgr.n_slots == 8
    assert int(mgr.cache["lengths"].shape[0]) == mgr.n_slots + 1
    # bucketed index padding targets scratch
    idx = np.asarray(mgr.padded_idx([0, 1, 4]))
    assert idx.shape[0] == slot_bucket(3) == 4
    assert idx[-1] == SlotCacheManager.SCRATCH
    assert ModelRunner(cfg, params, max_len=MAX_LEN).slots is not mgr


def test_idx_memo_survives_admissions_and_selective_release():
    cfg = _tiny("attn")
    mgr = SlotCacheManager(cfg, MAX_LEN, n_slots=4)
    for r in (0, 1, 2):
        mgr.admit(r)
    idx01 = mgr.padded_idx([0, 1])
    idx2 = mgr.padded_idx([2])
    # admitting a new request must not evict hot decode-batch indices
    mgr.admit(7)
    assert mgr.padded_idx([0, 1]) is idx01
    assert mgr.padded_idx([2]) is idx2
    # releasing rid 1 drops only the batches that contained it
    mgr.release(1)
    assert (0, 1) not in mgr._idx_cache
    assert mgr.padded_idx([2]) is idx2
    # the freed slot re-issued to a new rid resolves correctly
    slot1 = mgr.admit(9)
    idx9 = np.asarray(mgr.padded_idx([9]))
    assert idx9[0] == slot1


def test_idx_memo_size_bounded():
    cfg = _tiny("attn")
    mgr = SlotCacheManager(cfg, MAX_LEN, n_slots=2)
    mgr.admit(0)
    mgr.admit(1)
    mgr.IDX_CACHE_MAX = 8
    for i in range(40):
        mgr.padded_idx([0] if i % 2 else [0, 1])
        mgr.padded_idx([1, 0] if i % 3 else [1])
        # unique keys: vary via tuple of repeated rids
        mgr.padded_idx([0] * (1 + i % 5))
    assert len(mgr._idx_cache) <= 8


def test_extend_snapshot_matches_decode_chain(pair):
    """Teacher-forcing a snapshot (draft-ahead warm-up) must land in the
    same state as decoding the same tokens one by one."""
    runner, ref, cfg = pair
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab, 9)
    runner.prefill_request(0, toks)
    chain = rng.integers(0, cfg.vocab, 4).astype(np.int32)

    snap_a = runner.speculative_caches([0])
    for t in chain:
        lg_a, snap_a = runner.decode([0], np.asarray([t]), caches=snap_a)

    snap_b = runner.speculative_caches([0])
    lg_b, snap_b = runner.extend_snapshot(snap_b, chain[None, :])
    np.testing.assert_allclose(lg_a[0], np.asarray(lg_b)[0, -1, : cfg.vocab],
                               atol=ATOL)

    # and chaining continues identically from both states
    nxt = int(rng.integers(0, cfg.vocab))
    la, _ = runner.decode([0], np.asarray([nxt]), caches=snap_a)
    lb, _ = runner.decode([0], np.asarray([nxt]), caches=snap_b)
    np.testing.assert_allclose(la[0], lb[0], atol=ATOL)
