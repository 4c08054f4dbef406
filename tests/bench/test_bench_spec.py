"""Everything BENCHMARK.json names loads by name: each configuration file,
each model family, each traffic mix and each per-layer metric's reader;
and the file keeps the shape the benchmark's contract gives it."""
import json
import os
import re
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, ROOT)

import pytest

from bench import agreement, families, run, traffic

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_loads_by_name(c):
    cfg = run.load_config(BENCH, c["name"], root=__import__("pathlib").Path(ROOT))
    assert cfg["name"] == c["name"]
    for m in [cfg["target"]] + agreement.drafter_list(cfg):
        # every size, rate and scale a family requires is positive
        for k in families.of(m).REQUIRED:
            v = m[k]
            assert isinstance(v, (bool, str)) or v > 0, (k, v)
    doms = cfg["agreement"]["domains"]
    assert doms[0][0] == 0 and doms[-1][1] == cfg["target"]["vocab"]
    assert all(a[1] == b[0] for a, b in zip(doms, doms[1:]))
    # every limit that the verdict reads
    for k in ("target_gap", "drafter_gap"):
        assert cfg["correct"][k] > 0


CONFIG_FILES = sorted(
    [os.path.join("bench", "configs", n)
     for n in os.listdir(os.path.join(ROOT, "bench", "configs"))]
    + [os.path.join("tests", "bench", "tiny_config.json")])


@pytest.mark.parametrize("path", CONFIG_FILES)
def test_every_model_entry_names_a_family_that_loads(path):
    """Every configuration file, with or without a cell: each model entry
    names its family, which loads with its adapter, and holds the
    family's keys."""
    with open(os.path.join(ROOT, path)) as f:
        cfg = json.load(f)
    for m in [cfg["target"]] + [d["model"] for d in cfg["drafters"]]:
        families.check_entry(m, path)
        assert set(families.of(m).REQUIRED) <= set(m)
        adapter = families.adapter(m["family"])
        assert callable(adapter.model_config)
        assert callable(adapter.program_params)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_mix_loads_by_name(w):
    mix = traffic.load_mix(w["traffic"])
    assert mix["loop"] in ("closed", "open")
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert w["chips"] in (1, 4)


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_reader_loads_and_reads_nothing_from_nothing(m):
    read = run.reader(m["name"])
    empty = {"records": [], "window_s": 1.0, "survived": 0,
             "invalidated": 0, "window_compiles": 0, "trace": None,
             "commits": [], "target": {}, "peak": {}, "mean_context": 0}
    v = read(empty)
    assert v is None or m["name"] == "window_compiles"


def test_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for w in m.get("workloads", [x["name"] for x in BENCH["workloads"]]):
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", [w])
    for w in BENCH["workloads"]:
        reported = [m for m in BENCH["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
