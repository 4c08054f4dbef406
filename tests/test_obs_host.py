"""Host regions (obs/trace.py `Tracer.region`): wall-clock spans of the
served path's host work, counted into the engine's registry and written
into the JAX profiler's trace.

* a region counts inclusive ms, self ms (inclusive minus its children on
  the same thread) and one call; a child on another thread is not a child;
* collections the engine thread makes inside `gc_regions` are `host.gc`
  regions, those of other threads are not counted;
* regions are inert with tracing off, and without a registry (the
  simulated clocks' export stays free of wall time);
* the async served path runs every declared span of its path, each
  `draft.*` child within `engine.draft`, records carry the step's span ms,
  and `draft.node_busy_frac{node=i}` is node i's own measured work;
* a region is a host event in a profiler trace.
"""
import gc
import glob
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from conftest import TINY_MAX_LEN as MAX_LEN, tiny_model_cfg as _tiny
from repro.config import CoSineConfig, ModelConfig
from repro.models import model as M
from repro.obs import HOST_SPANS, MetricsRegistry, Tracer
from repro.obs.trace import (DRAFT_SPANS, ENGINE_SPANS, NODE_WORK_SPANS,
                             SERVER_SPANS)
from repro.serving.engine import SpeculativeEngine


def _host_counters(reg: MetricsRegistry) -> dict:
    return {k: v for k, v in reg.to_dict()["counters"].items()
            if k.startswith("host.")}


def test_region_counts_inclusive_self_and_calls():
    reg = MetricsRegistry()
    tr = Tracer(metrics=reg)
    for _ in range(2):
        with tr.region("engine.draft", cohort=3):
            time.sleep(0.002)
            with tr.region("draft.decode", node=1):
                time.sleep(0.004)
    v = lambda name, span: reg.value(name, span=span)
    assert v("host.calls", "engine.draft") == 2
    assert v("host.calls", "draft.decode") == 2
    outer, inner = v("host.ms", "engine.draft"), v("host.ms", "draft.decode")
    assert inner >= 8.0 and outer >= inner + 4.0
    assert v("host.self_ms", "engine.draft") == pytest.approx(outer - inner)
    assert v("host.self_ms", "draft.decode") == pytest.approx(inner)
    assert reg.value("host.node_ms", node=1, span="draft.decode") == \
        pytest.approx(inner)
    assert tr.node_ms(1) == pytest.approx(inner)
    assert tr.node_ms(0) == 0.0
    assert tr.host_ms() == {"engine.draft": pytest.approx(outer),
                            "draft.decode": pytest.approx(inner)}
    # regions keep out of the span deque (the simulated-clock record)
    assert not tr.spans


def test_region_on_another_thread_is_not_a_child():
    reg = MetricsRegistry()
    tr = Tracer(metrics=reg)

    def server():
        with tr.region("server.verify"):
            time.sleep(0.005)

    with tr.region("engine.verify_wait"):
        th = threading.Thread(target=server)
        th.start()
        th.join()
    wait = reg.value("host.ms", span="engine.verify_wait")
    assert reg.value("host.self_ms", span="engine.verify_wait") == \
        pytest.approx(wait)
    assert reg.value("host.ms", span="server.verify") >= 5.0


def test_threads_with_their_own_spans_lose_no_counts():
    """The registry has no lock: each thread writes only its own spans'
    counters, so under a short switch interval, with more threads than
    cores, every region is still counted exactly once."""
    reg = MetricsRegistry()
    tr = Tracer(metrics=reg)
    n_threads, n = (os.cpu_count() or 4) + 4, 500

    def work(k):
        for _ in range(n):
            with tr.region(f"t{k}", node=k):
                with tr.region(f"t{k}.child"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ths = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in ths)
    for k in range(n_threads):
        assert reg.value("host.calls", span=f"t{k}") == n
        assert reg.value("host.calls", span=f"t{k}.child") == n
        assert reg.value("host.self_ms", span=f"t{k}") <= \
            reg.value("host.ms", span=f"t{k}")
    assert len(tr.host_ms()) == 2 * n_threads


def test_gc_regions_count_the_calling_threads_collections():
    reg = MetricsRegistry()
    tr = Tracer(metrics=reg)
    n0 = len(gc.callbacks)
    was_on = gc.isenabled()
    gc.disable()        # only the explicit collections below
    try:
        with tr.gc_regions():
            assert len(gc.callbacks) == n0 + 1
            with tr.region("engine.finalize"):
                gc.collect()
            th = threading.Thread(target=gc.collect)
            th.start()
            th.join()
    finally:
        if was_on:
            gc.enable()
    assert len(gc.callbacks) == n0
    assert reg.value("host.calls", span="host.gc") == 1
    gc_ms = reg.value("host.ms", span="host.gc")
    assert reg.value("host.self_ms", span="engine.finalize") == \
        pytest.approx(reg.value("host.ms", span="engine.finalize") - gc_ms)
    gc.collect()        # after the block: not counted
    assert reg.value("host.calls", span="host.gc") == 1


@pytest.mark.parametrize("enabled,with_registry", [(False, True),
                                                   (True, False)])
def test_regions_are_inert_off_or_without_a_registry(enabled, with_registry):
    reg = MetricsRegistry()
    tr = Tracer(enabled=enabled, metrics=reg if with_registry else None)
    with tr.gc_regions():
        with tr.region("engine.walk", cohort=1):
            with tr.region("draft.decode", node=0):
                gc.collect()
    assert not tr.regions_live
    assert _host_counters(reg) == {}
    assert tr.host_ms() == {}
    assert tr.node_ms(0) == 0.0


def test_region_is_a_host_event_in_the_profiler_trace(tmp_path):
    tr = Tracer(metrics=MetricsRegistry())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.region("engine.walk", cohort=7):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    found = [e for p in pd.planes for ln in p.lines for e in ln.events
             if e.name == "engine.walk"]
    assert len(found) == 1 and found[0].duration_ns >= 2e6


def test_span_names_are_declared_once():
    assert len(set(HOST_SPANS)) == len(HOST_SPANS)
    assert set(NODE_WORK_SPANS) <= set(DRAFT_SPANS)
    assert all(s.startswith("engine.") for s in ENGINE_SPANS)
    assert all(s.startswith("server.") for s in SERVER_SPANS)


# ------------------------------------------------------- served path

@pytest.fixture(scope="module")
def models():
    tcfg = _tiny("attn")
    tparams = M.init_params(jax.random.PRNGKey(0), tcfg)
    dcfg = ModelConfig(name="tiny-draft", family="dense", n_layers=1,
                       d_model=48, n_heads=2, n_kv_heads=2, head_dim=16,
                       d_ff=96, vocab=50, tie_embeddings=True,
                       dtype="float32")
    drafters = [(dcfg, M.init_params(jax.random.PRNGKey(i + 1), dcfg),
                 f"d{i}") for i in range(2)]
    return (tcfg, tparams), drafters


def _serve(models, backend, **cos_kw):
    target, drafters = models
    cos = CoSineConfig(n_drafters=2, draft_len=4, drafters_per_request=2,
                       tree_width=2, **cos_kw)
    eng = SpeculativeEngine(target, drafters, cos, strategy="cosine",
                            max_len=MAX_LEN, backend=backend)
    rng = np.random.default_rng(3)
    for _ in range(3):
        eng.submit(rng.integers(1, 50, 8).tolist(), max_new_tokens=10)
    eng.run()
    eng.backend.shutdown()
    return eng


@pytest.fixture(scope="module")
def served(models):
    return _serve(models, "async")


def test_async_path_runs_every_declared_span(served):
    reg = served.metrics
    calls = {s: reg.value("host.calls", span=s) for s in HOST_SPANS}
    # every span of the closed-loop path (no arrival lull; a collection
    # need not happen)
    expected = set(HOST_SPANS) - {"engine.lull", "host.gc"}
    assert {s for s in expected if calls[s] <= 0} == set()


def test_draft_children_within_engine_draft_and_self_within_inclusive(
        served):
    reg = served.metrics
    draft = reg.value("host.ms", span="engine.draft")
    for s in DRAFT_SPANS:
        assert 0 < reg.value("host.ms", span=s) <= draft
    assert sum(reg.value("host.ms", span=s) for s in DRAFT_SPANS) <= draft
    for s in HOST_SPANS:
        inc = reg.value("host.ms", span=s)
        assert 0.0 <= reg.value("host.self_ms", span=s) <= inc + 1e-9


def test_records_carry_their_steps_span_ms(served):
    recs = served.stats.records
    assert recs and all(r.host_ms for r in recs)
    tot = served.tracer.host_ms()
    for s in ENGINE_SPANS:
        per_rec = sum(r.host_ms.get(s, 0.0) for r in recs)
        assert per_rec <= tot.get(s, 0.0) + 1e-6
    assert all("engine.walk" in r.host_ms and "engine.commit" in r.host_ms
               for r in recs)


def test_node_busy_gauge_is_each_nodes_own_work(served):
    g = served.metrics.to_dict()["gauges"]
    fracs = [g[f"draft.node_busy_frac{{node={i}}}"] for i in range(2)]
    assert all(0.0 < f <= 1.0 for f in fracs)
    # the aggregate is what the scheduler sees; the gauge is per node
    assert fracs[0] != fracs[1]
    assert served.tracer.node_ms(0) > 0.0


def test_tracing_off_writes_no_host_counters(models):
    eng = _serve(models, "async", enable_tracing=False)
    assert _host_counters(eng.metrics) == {}
    assert all(not r.host_ms for r in eng.stats.records)
    assert not any(k.startswith("draft.node_busy_frac")
                   for k in eng.metrics.to_dict()["gauges"])


def test_simulated_clocks_write_no_host_counters(models):
    eng = _serve(models, None)
    assert _host_counters(eng.metrics) == {}
    assert all(not r.host_ms for r in eng.stats.records)
