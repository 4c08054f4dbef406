"""The program's host spans as the benchmark reads them (bench/spans.py):

* the four per-layer readers on hand-made records, and nothing where the
  records carry no host spans (a program without them);
* device-idle time named by the innermost span over it: a program span
  nested in `step` names the gap, `step` names what no program span
  covers, 'none' what no span covers;
* the tiny configuration's engine (tests/bench/tiny_config.json) served a
  few steps on the async backend under the benchmark's `window` and
  `step` spans, with a profiler trace on the CPU: every span of the path
  counted, the readers read its records, and the program's spans sit on
  the engine thread's line of the trace, inside the `step` spans.
"""
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

import jax
import pytest

from bench import run, serve, spans, trace
from repro.obs import HOST_SPANS
from repro.obs.trace import DRAFT_SPANS, ENGINE_SPANS, SERVER_SPANS

with open(os.path.join(os.path.dirname(__file__), "tiny_config.json")) as f:
    TINY = json.load(f)

MIX = {"loop": "closed", "block": 4,
       "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 24},
       "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 24},
       "domains": "drafters", "random_token_share": 0.1, "requests": 64}

READERS = {"draft_host_ms": ("engine.draft",),
           "walk_host_ms": ("engine.logits_readback", "engine.walk"),
           "verify_wait_ms": ("engine.verify_wait", "engine.resolve"),
           "commit_host_ms": ("engine.commit",)}


def rec(**ms):
    return SimpleNamespace(host_ms=ms)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_its_spans_ms_per_verification(name):
    a, *b = READERS[name]
    recs = [rec(**{a: 10.0, "draft.decode": 99.0}),
            rec(**{a: 30.0, **{s: 5.0 for s in b}}),
            rec(**{"engine.plan": 1.0})]
    want = (40.0 + 5.0 * len(b)) / 3
    assert run.reader(name)({"records": recs}) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("records", [
    [],                                               # no verification
    [SimpleNamespace(batch=4), SimpleNamespace(batch=2)],  # no host spans
    [rec(), rec()],                                   # tracing off
])
def test_reader_reads_nothing_without_host_spans(name, records):
    assert run.reader(name)({"records": records}) is None


def test_idle_gap_inside_a_program_span_is_named_by_it():
    ops = [(0, 10, "a"), (40, 50, "b"), (80, 90, "c")]
    host = [(0, 100, "window"), (5, 60, "step"), (12, 45, "engine.draft"),
            (15, 35, "draft.decode"), (60, 95, "submit")]
    by = spans.idle_by_span(ops, host, 0, 100)
    # [10, 40]: step 10-12, engine.draft 12-15 and 35-40, decode 15-35
    assert by["draft.decode"] == pytest.approx(20e-9)
    assert by["engine.draft"] == pytest.approx(8e-9)
    assert by["step"] == pytest.approx(2e-9 + 10e-9)     # and [50, 60]
    assert by["submit"] == pytest.approx(25e-9)     # [60, 80], [90, 95]
    assert by["window"] == pytest.approx(5e-9)      # [95, 100]
    assert sum(by.values()) == pytest.approx(70e-9)
    assert spans.named_share(by, {"engine.draft", "draft.decode"}) == \
        pytest.approx(28 / 70)
    # no span over the first gap: 'none'
    assert spans.idle_by_span([(20, 30, "a")], [(25, 40, "step")], 0, 40) \
        == {"none": pytest.approx(20e-9), "step": pytest.approx(10e-9)}


def test_tiling_names_every_piece_by_the_innermost_span():
    host = [(0, 100, "step"), (10, 40, "engine.draft"),
            (20, 30, "draft.decode"), (40, 60, "engine.walk")]
    pieces = spans.tiling(host, 0, 120)
    assert pieces == [(0, 10, "step"), (10, 20, "engine.draft"),
                      (20, 30, "draft.decode"), (30, 40, "engine.draft"),
                      (40, 60, "engine.walk"), (60, 100, "step"),
                      (100, 120, "none")]
    assert spans.coverage(host, {"engine.draft", "engine.walk"}, "step",
                          0, 120) == pytest.approx(0.5)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The tiny cell's engine, a few steps under the benchmark's spans,
    the last of them traced."""
    cell = serve.Cell(TINY, MIX, 2**31 + 7)
    reqs = cell.requests(8)
    for r in reqs:      # short answers, so requests finish and are dropped
        r.max_new = 8
    serve.fill_closed(cell, reqs, time.monotonic())
    n0 = len(cell.eng.stats.records)
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    try:
        with serve.annotate("window"):
            for _ in range(6):
                cell.step()
    finally:
        jax.profiler.stop_trace()
    cell.eng.backend.sync()
    recs = cell.eng.stats.records[n0:]
    pd = trace.load(str(d))
    cell.shutdown()
    return cell, recs, pd


def test_tiny_engine_runs_the_paths_spans_and_readers_read_them(traced):
    cell, recs, _ = traced
    reg = cell.eng.metrics
    expected = (set(ENGINE_SPANS) | set(DRAFT_SPANS) | set(SERVER_SPANS)) \
        - {"engine.lull"}
    missing = {s for s in expected if reg.value("host.calls", span=s) <= 0}
    assert missing == set()
    assert recs
    for name in READERS:
        v = run.reader(name)({"records": recs})
        assert v is not None and v >= 0.0


def test_program_spans_sit_inside_step_on_the_engine_line(traced):
    _, _, pd = traced
    lines = spans.thread_lines(pd, lambda p, ln: p.startswith("/host:"),
                               set(HOST_SPANS) | set(trace.HOST_SPANS))
    engine = [ev for ev in lines if trace.window_bounds(ev) is not None]
    assert len(engine) == 1
    host = engine[0]
    names = {s[2] for s in host}
    assert {"step", "engine.draft", "draft.decode", "engine.walk"} <= names
    assert not names & set(SERVER_SPANS)
    server = {s[2] for ev in lines if ev is not host for s in ev}
    assert "server.verify" in server
    lo, hi = trace.window_bounds(host)
    assert 0.8 < spans.coverage(host, set(HOST_SPANS), "step", lo, hi) <= 1.0
    wall = spans.by_label(spans.tiling(host, lo, hi))
    assert sum(wall.values()) == pytest.approx((hi - lo) * 1e-9)
    assert set(wall) <= set(HOST_SPANS) | set(trace.HOST_SPANS) | {"none"}
