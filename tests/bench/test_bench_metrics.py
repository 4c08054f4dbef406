"""Per-layer readers on hand-made records: the verifier's idle share is
the union of the records' idle spans inside the window, so overlapping
spans count once and the share never passes 100%."""
import os
import sys
from types import SimpleNamespace

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

import pytest

from bench import run


def rec(start_ms, idle_ms):
    return SimpleNamespace(verify_start_ms=start_ms, verify_idle_ms=idle_ms)


@pytest.mark.parametrize("records,share", [
    # disjoint spans: 100 + 200 ms of a 1000 ms window
    ([rec(300, 100), rec(800, 200)], 30.0),
    # overlapping spans count once: [100, 400] and [300, 600]
    ([rec(400, 300), rec(600, 300)], 50.0),
    # one span inside another
    ([rec(900, 800), rec(500, 100)], 80.0),
    # spans are clipped to the window [0, 1000]
    ([rec(200, 700), rec(1500, 900)], 60.0),
    # spans covering the whole window, twice over: 100%, never more
    ([rec(1000, 1000), rec(1000, 1000), rec(1200, 1300)], 100.0),
])
def test_verifier_idle_share_is_the_union_in_the_window(records, share):
    ctx = {"records": records, "window_s": 1.0, "window_ms": (0.0, 1000.0)}
    assert run.reader("verifier_idle_share")(ctx) == pytest.approx(share)
