"""Greedy drafting on the device: the drafters' decode program on a
snapshot also computes each row's argmax token and its softmax
probability, and the draft path reads back only those.

Covers: the picked token and probability against the host's pick of the
same program's logits (attention and SSM drafters, plain and int8, a
batch below its row bucket); a fused K-step cohort draft against the
host-sampled path it replaces; one compiled program per row bucket for
the logits-returning and the greedy call; and the bytes the draft path
copies to the host (`draft.readback_bytes`), with the target's commit
tails unchanged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_MAX_LEN as MAX_LEN, tiny_model_cfg as _tiny
from repro.config import CoSineConfig
from repro.models import model as M
from repro.models.quantize import resolve_drafter_quant
from repro.serving.engine import SpeculativeEngine
from repro.serving.runner import ModelRunner, slot_bucket


def _drafter(kind, quant):
    cfg = _tiny(kind)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    if quant == "int8":
        [(cfg, params, _)] = resolve_drafter_quant(
            [(cfg.with_overrides(quant="int8"), params, "d")])
    return cfg, params


def _host_pick(lg):
    """The host-sampled path: softmax over the copied logits, argmax and
    the probability at it."""
    probs = jax.nn.softmax(jnp.asarray(lg), -1)
    tok = np.asarray(jnp.argmax(probs, -1))
    conf = np.asarray(jnp.take_along_axis(probs, jnp.asarray(tok)[:, None],
                                          -1))[:, 0]
    return tok, conf


@pytest.mark.parametrize("quant", ["none", "int8"])
@pytest.mark.parametrize("kind", ["attn", "ssm"])
def test_greedy_step_picks_argmax_and_its_probability(kind, quant):
    """Three requests (a 4-row bucket, one padded row), three chained
    steps: the device's token is the argmax of the logits `decode`
    returns for the same inputs, and its probability the host softmax
    there within 1e-6 relative."""
    cfg, params = _drafter(kind, quant)
    runner = ModelRunner(cfg, params, max_len=MAX_LEN, n_slots=4)
    rng = np.random.default_rng(0)
    rids = [0, 1, 2]
    for rid in rids:
        runner.prefill_request(rid, rng.integers(0, cfg.vocab, 5 + 2 * rid))
    snap_host = runner.speculative_caches(rids)
    snap_dev = runner.speculative_caches(rids)
    toks = rng.integers(0, cfg.vocab, len(rids)).astype(np.int32)
    for _ in range(3):
        lg, snap_host = runner.decode(rids, toks, caches=snap_host)
        dlg, snap_dev = runner.decode_device(toks, snap_dev)
        assert dlg.shape == (slot_bucket(len(rids)), cfg.vocab)
        tok, conf = runner.pick(dlg)
        assert tok.shape == conf.shape == (slot_bucket(len(rids)),)
        assert tok.dtype == np.int32 and conf.dtype == np.float32
        tok, conf = tok[: len(rids)], conf[: len(rids)]
        np.testing.assert_array_equal(tok, np.argmax(lg, -1))
        e = np.exp(lg.astype(np.float64) - lg.max(-1, keepdims=True))
        ref = e[np.arange(len(rids)), tok] / e.sum(-1)
        np.testing.assert_allclose(conf, ref, rtol=1e-6)
        toks = tok


def test_pick_of_other_logits_is_taken_on_the_host():
    """Logits that are not the last decode's own (here rolled by one
    token) are picked from their values, not from the program's pick."""
    cfg, params = _drafter("attn", "none")
    runner = ModelRunner(cfg, params, max_len=MAX_LEN, n_slots=2)
    runner.prefill_request(0, np.arange(1, 7))
    lg, _ = runner.decode_device(np.asarray([3]),
                                 runner.speculative_caches([0]))
    rolled = jnp.roll(lg, 1, axis=-1)
    tok, conf = runner.pick(rolled)
    x = np.asarray(rolled)
    np.testing.assert_array_equal(tok, np.argmax(x, -1))
    np.testing.assert_allclose(conf, _host_pick(x)[1], rtol=1e-6)


def _engine(drafter_kind, strategy="cosine", backend=None):
    tcfg = _tiny("attn")
    dcfg = _tiny(drafter_kind)
    drafters = [(dcfg, M.init_params(jax.random.PRNGKey(i + 1), dcfg),
                 f"d{i}") for i in range(2)]
    cos = CoSineConfig(n_drafters=2, draft_len=8, drafters_per_request=2,
                       tree_width=2)
    return SpeculativeEngine((tcfg, M.init_params(jax.random.PRNGKey(0),
                                                  tcfg)),
                             drafters, cos, strategy=strategy,
                             max_len=MAX_LEN, seed=0, backend=backend)


def _prefilled(eng, n=3):
    rng = np.random.default_rng(3)
    for i in range(n):
        eng.submit(rng.integers(1, 50, 8).tolist(), max_new_tokens=10,
                   arrival_ms=float(i * 5))
    batch = eng.pool.pending(float("inf"))
    for r in batch:
        eng._ensure_prefilled(r)
    return batch


@pytest.mark.parametrize("assumed", [0, 2])
@pytest.mark.parametrize("kind", ["attn", "ssm"])
def test_fused_chain_matches_host_sampled_path(kind, assumed):
    """An 8-step fused cohort draft, with or without an assumed chain
    teacher-forced first (draft-ahead), proposes what the host-sampled
    path proposes: the same fused chain, per-drafter tokens and consumed
    chains, and confidences within 1e-6 relative."""
    eng = _engine(kind)
    batch = _prefilled(eng)
    rng = np.random.default_rng(7)
    optimistic = {r.rid: rng.integers(1, 50, (2, assumed)).astype(np.int32)
                  for r in batch} if assumed else None
    gammas = [8, 5, 8]
    device = eng._draft_entries(batch, gammas, optimistic=optimistic)

    def host_sampled(di, rids, tokens, snap):
        lg, snap = eng.backend.drafters[di].decode(rids, tokens, caches=snap)
        return (*_host_pick(lg), snap)

    eng.backend.draft_decode_greedy = host_sampled
    host = eng._draft_entries(batch, gammas, optimistic=optimistic)
    for a, b in zip(device, host):
        np.testing.assert_array_equal(a.fused_t, b.fused_t)
        np.testing.assert_array_equal(a.d_toks, b.d_toks)
        np.testing.assert_array_equal(a.d_chains, b.d_chains)
        np.testing.assert_allclose(a.fused_p, b.fused_p, rtol=1e-6)
        np.testing.assert_allclose(a.d_confs, b.d_confs, rtol=1e-6)


def _count_compiles():
    n = {"compiles": 0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n["compiles"] += 1

    return n, listen


def test_logits_and_greedy_step_share_one_program_per_bucket():
    """The logits-returning `draft_decode` (how a set-up sweep warms the
    drafters) and the engine's greedy step run one compiled program per
    row bucket: once either has run at a bucket, neither compiles
    again there, by JAX's backend-compile event."""
    eng = _engine("attn")
    be = eng.backend
    rids = [r.rid for r in _prefilled(eng, n=4)]
    n, listen = _count_compiles()
    for b in (1, 2, 3, 4):
        zeros = np.zeros(b, np.int32)
        snaps = [be.draft_snapshot(0, rids[:b]) for _ in range(3)]
        if b in (1, 2, 3):     # first call at the bucket: warms it
            be.draft_decode(0, rids[:b], zeros, snaps[0])
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            tok, conf, _ = be.draft_decode_greedy(0, rids[:b], zeros,
                                                  snaps[1])
            be.draft_decode(0, rids[:b], zeros, snaps[2])
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        assert tok.shape == conf.shape == (b,)
        assert n["compiles"] == 0, b


@pytest.mark.parametrize("backend", ["sim", "async"])
def test_draft_path_reads_back_only_tokens_and_probabilities(backend):
    """`draft.readback_bytes{node=}` counts rows x 8 bytes per greedy
    step and nothing for a teacher-forced extend or the drafters'
    commit; the target's commit still returns its tail logits, equal to
    a batch-1 reference extend."""
    eng = _engine("attn", backend=backend)
    try:
        be, m = eng.backend, eng.metrics
        batch = _prefilled(eng)
        rids = [r.rid for r in batch]
        rows = slot_bucket(len(rids))

        def readback(di):
            return m.value("draft.readback_bytes", node=di)

        for di in range(len(be.drafters)):
            snap = be.draft_snapshot(di, rids)
            snap = be.draft_extend(di, snap, np.ones((len(rids), 3), np.int32))
            assert readback(di) == 0
            for step in range(1, 3):
                tok, _, snap = be.draft_decode_greedy(
                    di, rids, np.ones(len(rids), np.int32), snap)
                assert readback(di) == step * rows * 8
        before = [readback(di) for di in range(len(be.drafters))]
        commit = {rid: [5, 6] for rid in rids}
        be.commit_drafters(commit)
        assert [readback(di) for di in range(len(be.drafters))] == before

        tails = be.commit_target(commit)
        tcfg, tparams = be.target.cfg, be.target.params
        for r in batch:
            cache = M.init_cache(tcfg, 1, MAX_LEN, dtype=jnp.float32)
            _, cache, _ = M.prefill(tparams, tcfg,
                                    jnp.asarray(r.prompt)[None, :], cache)
            lg, _, _ = M.extend(tparams, tcfg, jnp.asarray([[5, 6]]), cache)
            np.testing.assert_allclose(tails[r.rid],
                                       np.asarray(lg[0, -1, : tcfg.vocab]),
                                       atol=1e-5)
    finally:
        eng.backend.shutdown()
