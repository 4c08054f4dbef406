"""The trace reduction: busy union, idle share, per-program device time
and labelled idle gaps, on hand-made intervals and on a small profiler
trace recorded on the CPU (tests/bench/data/cpu_trace.xplane.pb: a
jitted matmul/tanh program run four times under 'step' spans inside a
'window' span, with 3 ms sleeps between)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")

CPU = trace.Selector(
    ops=lambda p, ln: p == "/host:CPU" and ln.startswith("tf_XLAPjRtCpu"),
    programs=lambda p, ln: p == "/host:CPU" and ln == "python",
    host=lambda p, ln: p == "/host:CPU" and ln == "python")


def test_busy_is_the_union_of_overlapping_ops():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (25, 28, "d")]
    assert trace.merge(ops) == [(0, 15), (20, 30)]
    assert trace.busy_ns(ops, 0, 40) == 25
    # clipped to the window
    assert trace.busy_ns(ops, 8, 22) == 9


def test_program_times_group_by_jit_name():
    progs = [(0, 1e6, "jit_slot_verify_chunk(12)"),
             (2e6, 5e6, "jit_slot_verify_chunk(12)"),
             (6e6, 7e6, "jit_decode_step.3"), (9e9, 1e10, "jit_extend(1)")]
    out = trace.program_times(progs, 0, 1e9)
    assert out["slot_verify_chunk"] == pytest.approx((4e-3, 2))
    assert out["decode_step"] == pytest.approx((1e-3, 1))
    assert "extend" not in out            # starts after the window


def test_idle_gaps_are_named_by_the_covering_host_span():
    ops = [(0, 10, "a"), (40, 50, "b")]
    host = [(0, 100, "window"), (10, 45, "step"), (45, 100, "submit")]
    gaps = trace.idle_gaps(ops, host, 0, 100)
    assert gaps[0] == ["submit", pytest.approx(50e-9)]
    assert gaps[1] == ["step", pytest.approx(30e-9)]


def test_recorded_cpu_trace_reduces():
    pd = trace.load(DATA)
    red = trace.reduce(pd, CPU)
    assert 0.003 * 3 < red["window_s"] < 5.0
    assert 0.0 < red["busy_s"] < red["window_s"]
    idle_share = 1.0 - red["busy_s"] / red["window_s"]
    assert 0.0 < idle_share < 1.0
    assert red["device_ops"] and red["device_ops"][0][1] > 0
    assert len(red["idle_gaps"]) <= 10
    labels = {g[0] for g in red["idle_gaps"]}
    assert labels <= set(trace.HOST_SPANS) | {"none"}
    assert "PjitFunction" in red["programs"]
