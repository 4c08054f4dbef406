"""A whole run at a size a test can hold: the cell's harness with the
look for a chip skipped (tests/bench/tiny_config.json: the qwen pair's
structure at tiny widths, on the CPU).

* the program passes; the fp8 control put in its place fails the limits,
  both in a run (`--control`, judged on the control's readings) and on
  fixed walks through every model's map (its readings at the cell's own
  size were made on the chip and are in PERF.md);
* with the timed path broken underneath, `correct` comes out false: a
  committed token altered where the acceptance walk produces it, and a
  drafter's proposal altered where its decode produces it.
"""
import json
import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

import jax.numpy as jnp
import numpy as np
import pytest

from bench import agreement, check, reference, run
from repro.core import tree as tree_mod
from repro.serving import backend as backend_mod

with open(os.path.join(os.path.dirname(__file__), "tiny_config.json")) as f:
    TINY = json.load(f)

MIX = {"loop": "closed", "block": 4,
       "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 24},
       "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 24},
       "domains": "drafters", "random_token_share": 0.1, "requests": 64}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E2E = [{"name": "tokens_per_s", "unit": "tokens/s"},
       {"name": "tpot_p50_ms", "unit": "ms"}, {"name": "setup_s", "unit": "s"}]


def one_run(seed, control=False):
    return run.run_cell(TINY, MIX, workload="tiny", seed=seed, seconds=12.0,
                        trace_on=False, e2e=E2E, per_layer=[], peak=PEAK,
                        control=control, log=lambda *a, **k: None)


@pytest.fixture(scope="module")
def control_run():
    """One run with the control read beside the program."""
    return one_run(11, control=True)


def test_program_passes(control_run):
    res = control_run
    rows = check.verdict(res["readings"], TINY["correct"])
    assert all(r["ok"] for r in rows), rows
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"target_gap", "drafter_gap",
                                  "target_positions", "drafter_positions"}
    assert set(res["metrics"]) == {"tokens_per_s", "tpot_p50_ms", "setup_s"}
    assert res["metrics"]["tokens_per_s"]["value"] > 0


def test_control_in_the_programs_place_is_not_correct(control_run):
    res = control_run
    r, c = res["readings"], res["checks"]
    assert not res["correct"]
    assert c["target_gap"]["value"] == r["target_control_gap"]
    assert c["drafter_gap"]["value"] == r["drafter_control_gap"]
    assert (c["target_gap"]["value"] > c["target_gap"]["limit"]
            or c["drafter_gap"]["value"] > c["drafter_gap"]["limit"])


def test_fp8_control_fails_both_limits():
    """The control in the program's place, read at every position of
    fixed walks through each domain: for the target and for each drafter,
    the widest gap of the tokens it puts first lies above the limit that
    the program keeps."""
    canon, tables = agreement.plant_all(11, TINY)
    models = [TINY["target"]] + agreement.drafter_list(TINY)
    succ = np.asarray(tables["succ"])
    rng = np.random.default_rng(0)
    worst = [0.0] * len(models)
    for lo, hi in TINY["agreement"]["domains"]:
        t = int(rng.integers(lo, hi))
        seq = [t]
        for _ in range(TINY["serving"]["max_len"] - 1):
            t = int(succ[t]) if rng.random() > 0.1 else int(
                rng.integers(lo, hi))
            seq.append(t)
        for i, m in enumerate(models):
            r = reference.readings(canon[i], m, seq, seq, control=True)
            worst[i] = max(worst[i], float(np.max(r["control_gap"])))
    assert worst[0] > TINY["correct"]["target_gap"]
    assert min(worst[1:]) > TINY["correct"]["drafter_gap"]


def test_altered_committed_token_is_not_correct(monkeypatch):
    walk = tree_mod.accept_tree_greedy
    vocab = TINY["target"]["vocab"]

    def altered(tree, node_argmax, entry_argmax):
        acc, nodes, corr = walk(tree, node_argmax, entry_argmax)
        return acc, nodes, (int(corr) + 1) % vocab

    monkeypatch.setattr(tree_mod, "accept_tree_greedy", altered)
    res = one_run(12)
    assert not res["correct"]
    assert res["checks"]["target_gap"]["value"] > \
        res["checks"]["target_gap"]["limit"]


def test_altered_drafter_proposal_is_not_correct(monkeypatch):
    """Each drafter decode's logits rolled by one token: every proposal is
    the token after the drafter's own best. Served streams stay exact (the
    target verifies them), so only the drafters' comparison can see it."""
    decode = backend_mod.AsyncJaxBackend.draft_decode

    def altered(self, di, rids, tokens, snap):
        lg, caches = decode(self, di, rids, tokens, snap)
        return jnp.roll(jnp.asarray(lg), 1, axis=-1), caches

    monkeypatch.setattr(backend_mod.AsyncJaxBackend, "draft_decode", altered)
    res = one_run(13)
    assert not res["correct"]
    c = res["checks"]
    assert c["target_gap"]["value"] <= c["target_gap"]["limit"]
    assert c["drafter_gap"]["value"] > c["drafter_gap"]["limit"]
