"""The planted agreement, at the configurations' published widths and
vocabularies with depth cut to one layer (the CPU cannot run 40 layers at
these widths in a test's time):

* every tensor has the shape and dtype `init_params` gives it;
* on teacher-forced walks, each drafter's greedy token equals the
  target's at about `p_in` on its own domain and `p_out` elsewhere;
* the target's greedy continuation stays in the request's domain and
  repeats no token (no cycle) over 40 tokens.
"""
import copy
import json
import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

import jax
import numpy as np
import pytest

from bench import agreement, program, reference
from repro.models import model as M

SEED = 20260917


def config(name, layers=1):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg["target"]["n_layers"] = layers
    for d in cfg["drafters"]:
        d["model"]["n_layers"] = layers
    return cfg


@pytest.fixture(scope="module")
def danube():
    cfg = config("danube3-4b_danube3-500mx4")
    return cfg, agreement.plant_all(SEED, cfg)


@pytest.fixture(scope="module")
def qwen():
    cfg = config("qwen1.5-4b_qwen2-0.5bx2")
    return cfg, agreement.plant_all(SEED, cfg)


def walk(tables, dom_slice, n, rng):
    succ = np.asarray(tables["succ"])
    lo, hi = dom_slice
    t = int(rng.integers(lo, hi))
    seq = [t]
    for _ in range(n - 1):
        t = int(succ[t]) if rng.random() > 0.1 else int(rng.integers(lo, hi))
        seq.append(t)
    return seq


def rates(cfg, canon, tables, length):
    """Per drafter: agreement with the target on its domain and off it,
    over teacher-forced walks through every domain."""
    models = [cfg["target"]] + agreement.drafter_list(cfg)
    rng = np.random.default_rng(1)
    hits = {}
    for di, (lo, hi) in enumerate(cfg["agreement"]["domains"]):
        seq = walk(tables, (lo, hi), length, rng)
        t = reference.readings(canon[0], models[0], seq, seq)["argmax"]
        for j, m in enumerate(models[1:]):
            d = reference.readings(canon[1 + j], m, seq, seq)["argmax"]
            key = (j, di == m["domain"])
            a, n = hits.get(key, (0, 0))
            hits[key] = (a + int(np.sum(d == t)), n + len(seq))
    return {k: a / n for k, (a, n) in hits.items()}


@pytest.mark.parametrize("which", ["danube", "qwen"])
def test_shapes_and_dtypes_equal_init_params(which, request):
    cfg, (canon, _) = request.getfixturevalue(which)
    models = [cfg["target"]] + agreement.drafter_list(cfg)
    for m, c in zip(models, canon):
        pc = program.model_config(m)
        want = jax.eval_shape(lambda k: M.init_params(k, pc),
                              jax.random.PRNGKey(0))
        got = program.program_params(c, m)
        assert jax.tree.structure(want) == jax.tree.structure(got)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert a.shape == b.shape and a.dtype == b.dtype


@pytest.mark.parametrize("which,length", [("danube", 400), ("qwen", 192)])
def test_agreement_rates_follow_the_configuration(which, length, request):
    cfg, (canon, tables) = request.getfixturevalue(which)
    ag = cfg["agreement"]
    r = rates(cfg, canon, tables, length)
    for (j, own), v in r.items():
        p = ag["p_in"] if own else ag["p_out"]
        # the target itself leaves its planted successor for the rival at
        # small gaps, so agreement sits a little under the planted rate
        assert p - 0.15 <= v <= p + 0.08, (j, own, v, p)


def test_continuation_stays_in_domain_without_cycles(danube):
    cfg, (canon, tables) = danube
    dom = np.asarray(tables["dom"])
    rng = np.random.default_rng(2)
    doms = cfg["agreement"]["domains"]
    # a drafter's domain and the domain no drafter knows
    for di in (0, len(doms) - 1):
        prompt = walk(tables, doms[di], 16, rng)
        out = reference.greedy(canon[0], cfg["target"], prompt, 40, 64)
        assert (dom[out] == di).all()
        assert len(set(out)) == len(out)
