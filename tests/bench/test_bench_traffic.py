"""The traffic generator: every seed gets the same work in another order,
open-loop arrivals keep the mix's mean rate, and prompts walk the planted
successor map inside the request's domain."""
import os
import sys
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np
import pytest

from bench import traffic

SLICES = [(0, 100), (100, 200), (200, 300)]
SUCC = np.asarray([(i // 100) * 100 + (i % 100 + 1) % 100 for i in range(300)])


@pytest.mark.parametrize("name", ["batch-indomain", "chat-indomain",
                                  "batch-offdomain"])
def test_every_seed_gets_the_same_work(name):
    mix = traffic.load_mix(name)
    doms = [0, 1] if mix["domains"] == "drafters" else [2]
    n = 2 * mix["block"]
    a, b = (traffic.schedule(mix, seed=s, n_requests=n, domains=doms,
                             succ=SUCC, slices=SLICES)
            for s in (1, 2 ** 31 + 12345))
    key = lambda rs: Counter((len(r.prompt), r.max_new, r.domain) for r in rs)
    assert key(a) == key(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    for r in a:
        lo, hi = SLICES[r.domain]
        assert all(lo <= t < hi for t in r.prompt)
        lens = mix["prompt"]
        assert lens["min"] <= len(r.prompt) <= lens["max"]


def test_open_arrivals_keep_the_rate_and_bursts():
    mix = dict(traffic.load_mix("chat-indomain"), rate_per_s=2.0)
    rs = traffic.schedule(mix, seed=3, n_requests=4 * mix["block"],
                          domains=[0, 1], succ=SUCC, slices=SLICES)
    due = np.asarray([r.due_ms for r in rs])
    assert (np.diff(due) >= 0).all()
    assert due[-1] / 1e3 == pytest.approx(len(rs) / 2.0, rel=0.1)
    # bursts: some arrivals share an instant
    assert (np.diff(due) == 0).sum() >= len(rs) // mix["burst_every"]


def test_prompts_follow_the_successor_map():
    mix = dict(traffic.load_mix("batch-indomain"), random_token_share=0.0)
    r = traffic.schedule(mix, seed=5, n_requests=1, domains=[1], succ=SUCC,
                         slices=SLICES)[0]
    assert all(SUCC[a] == b for a, b in zip(r.prompt, r.prompt[1:]))
