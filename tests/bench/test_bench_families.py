"""Model families: the harness finds each model's family by the name its
configuration gives, and names none itself.

* The dense family is the harness's dense code moved, not rewritten: the
  weights `plant_all` draws and the reference's readings equal digests
  computed with the harness as it was before families existed (the
  harness of that commit, run on the CPU over the same configurations,
  seed and tokens as below).
* A family that only the tests know (`sliding_family.py`: a 16-position
  sliding window, one dict per layer with fused matrices), bound to the
  name `sliding` by the test, is served through `run.run_cell` on the
  CPU: `correct` holds for the program and fails for the fp8 control.
* A model entry without a family, with an unknown one or without one of
  its family's keys fails to load, naming its file; the benchmark's side
  of a family imports nothing of the program.
"""
import ast
import copy
import hashlib
import json
import os
import sys
from pathlib import Path

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(__file__))

import jax
import numpy as np
import pytest

import sliding_family
import sliding_family_program
from bench import agreement, check, families, reference, run

SEED = 3000001500      # above 2**31: both halves of the seed's key
BENCH_DIR = Path(ROOT).resolve() / "bench"

# sha256 of every array, computed on the CPU before the dense code moved
PLANTED = {
    "tiny": "592b88ac3d24c6a4a2c2d924b1142a7ece83cb3dc37a706a58b23f01616e7a9d",
    "qwen1": "49c32a73e15e7d1f63d4387ede301408c13bba77465b23eed5a421a5d6ec2f6e",
}
READINGS = [
    "10be587275adea10bbd3c90e0ab1599575350d6185e13c38b954304bb12fa720",
    "1e175cea33bf0f88bf4397df3dd4c17b6d30f1c231d25765605ed26177f89249",
    "92abd12effffb4cb91ef8d05b3aed747eeffc0e5ce303ea48022b61e5457c890",
]

with open(os.path.join(os.path.dirname(__file__), "tiny_config.json")) as f:
    TINY = json.load(f)


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes, by path."""
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda p: jax.tree_util.keystr(p[0])):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def qwen_one_layer():
    with open(BENCH_DIR / "configs" / "qwen1.5-4b_qwen2-0.5bx2.json") as f:
        cfg = json.load(f)
    cfg["target"]["n_layers"] = 1
    for d in cfg["drafters"]:
        d["model"]["n_layers"] = 1
    return cfg


def walk(succ, length, seed):
    """A fixed sequence: the target's map with 10% random tokens."""
    rng = np.random.default_rng(seed)
    t = int(rng.integers(1000, 2000))
    seq = [t]
    for _ in range(length - 1):
        t = int(succ[t]) if rng.random() > 0.1 else int(
            rng.integers(0, TINY["target"]["vocab"]))
        seq.append(t)
    return seq


# ------------------------------------------------------- dense identity

@pytest.mark.parametrize("name", ["tiny", "qwen1"])
def test_dense_planting_is_unchanged(name):
    cfg = TINY if name == "tiny" else qwen_one_layer()
    canon, tables = agreement.plant_all(SEED, cfg)
    assert digest({"canon": canon, "tables": tables}) == PLANTED[name]


def test_dense_reference_readings_are_unchanged():
    canon, tables = agreement.plant_all(SEED, TINY)
    seq = walk(np.asarray(tables["succ"]), TINY["serving"]["max_len"], 5)
    models = [TINY["target"]] + agreement.drafter_list(TINY)
    got = [digest(reference.readings(c, m, seq, seq, control=True))
           for c, m in zip(canon, models)]
    assert got == READINGS


# ------------------------------------------------ a family of the tests

MIX = {"loop": "closed", "block": 4,
       "prompt": {"median": 16, "sigma": 0.5, "min": 8, "max": 24},
       "output": {"median": 16, "sigma": 0.5, "min": 8, "max": 24},
       "domains": "drafters", "random_token_share": 0.1, "requests": 64}
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E2E = [{"name": "tokens_per_s", "unit": "tokens/s"},
       {"name": "setup_s", "unit": "s"}]


@pytest.fixture
def sliding(monkeypatch):
    """The tiny configuration with a target of the family `sliding`,
    which exists only while the test runs."""
    monkeypatch.setitem(sys.modules, "bench.families.sliding",
                        sliding_family)
    monkeypatch.setitem(sys.modules, "bench.families.sliding_program",
                        sliding_family_program)
    cfg = copy.deepcopy(TINY)
    cfg["target"].update(family="sliding", sliding_window=16)
    return cfg


def test_sliding_reference_differs_from_dense_past_the_window(sliding):
    """The same planted weights in both layouts: the two references agree
    while every key is inside the window and part after it."""
    m = sliding["target"]
    canon, tables = agreement.plant_all(SEED, sliding)
    w = canon[0]
    assert "blocks" in w and "layers" not in w
    as_dense = sliding_family_program.to_dense(w, m)
    seq = walk(np.asarray(tables["succ"]), 64, 6)
    win = reference.readings(w, m, seq, seq)["best"]
    full = reference.readings(as_dense, dict(m, family="dense"), seq,
                              seq)["best"]
    np.testing.assert_allclose(win[:16], full[:16], atol=1e-4)
    assert np.max(np.abs(win[16:] - full[16:])) > 1e-2


def test_unnamed_family_served_through_run_cell(sliding):
    """Served on the CPU as `attention="swa"`: the program keeps the
    limits, and the fp8 control in its place does not."""
    res = run.run_cell(sliding, MIX, workload="sliding", seed=15,
                       seconds=8.0, trace_on=False, e2e=E2E, per_layer=[],
                       peak=PEAK, control=True, log=lambda *a, **k: None)
    rows = check.verdict(res["readings"], sliding["correct"])
    assert all(r["ok"] for r in rows), rows
    assert res["readings"]["target_positions"] > 0
    assert not res["correct"]
    assert res["checks"]["target_gap"]["value"] > \
        res["checks"]["target_gap"]["limit"]


def test_counts_dispatch_to_the_family(sliding):
    from bench import roofline
    m = sliding["target"]
    d = dict(m, family="dense")
    assert roofline.weight_bytes(m) == roofline.weight_bytes(d)
    assert roofline.token_flops(m, 15) == roofline.token_flops(d, 15)
    assert roofline.token_flops(m, 500) == roofline.token_flops(d, 15)


# ------------------------------------------------------------ loading

def write_config(tmp_path, edit):
    cfg = copy.deepcopy(TINY)
    edit(cfg)
    (tmp_path / "c.json").write_text(json.dumps(cfg))
    return {"configs": [{"name": "c", "file": "c.json"}]}


@pytest.mark.parametrize("edit,says", [
    (lambda c: c["target"].pop("family"), "names no family"),
    (lambda c: c["drafters"][0]["model"].pop("family"), "names no family"),
    (lambda c: c["target"].update(family="nonesuch"), "no family"),
    (lambda c: c["target"].update(family="../dense"), "not a family"),
    (lambda c: c["target"].pop("d_ff"), "d_ff"),
], ids=["target", "drafter", "unknown", "path", "key"])
def test_bad_model_entry_fails_to_load_naming_the_file(tmp_path, edit, says):
    bench = write_config(tmp_path, edit)
    with pytest.raises(families.ConfigError, match=says) as e:
        run.load_config(bench, "c", root=tmp_path)
    assert "c.json" in str(e.value)


def module_imports(path: Path):
    """Every module name that a file imports, as written."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module)
            out.update(f"{node.module}.{a.name}" for a in node.names)
    return out


def bench_file(name: str):
    """The file of a module `bench.x.y`, or None."""
    parts = name.split(".")
    if parts[0] != "bench":
        return None
    p = BENCH_DIR.joinpath(*parts[1:])
    for f in (p.with_suffix(".py"), p / "__init__.py"):
        if f.is_file():
            return f
    return None


def reference_side():
    fams = [p for p in sorted((BENCH_DIR / "families").glob("*.py"))
            if not p.stem.endswith("_program") and p.stem != "__init__"]
    return fams + [Path(sliding_family.__file__)]


@pytest.mark.parametrize("path", reference_side(), ids=lambda p: p.name)
def test_reference_side_imports_nothing_of_the_program(path):
    """The family module and every benchmark module it reaches import
    neither `repro` nor the program's adapters."""
    seen, todo, names = set(), [path], set()
    while todo:
        f = todo.pop()
        if f in seen:
            continue
        seen.add(f)
        for n in module_imports(f):
            names.add(n)
            g = bench_file(n)
            if g is not None:
                todo.append(g)
    bad = sorted(n for n in names
                 if n.split(".")[0] == "repro" or n == "bench.program"
                 or n.endswith("_program"))
    assert not bad, (path.name, bad)
    assert BENCH_DIR / "reference.py" in seen
