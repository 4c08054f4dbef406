#!/usr/bin/env python3
"""CoSine chip benchmark: one run of one cell.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<name>.json`) under a traffic mix
(`bench/traffic/<name>.json`). The run plants the cell's weights from the
seed on the device, builds `SpeculativeEngine(strategy="cosine",
backend="async")`, warms up the shapes its traffic uses, serves the
traffic for `--seconds`, and then checks the served streams against the
plain reference. With `--trace 0` it reports the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics (each read by
`bench/metrics/<name>.py`) from a profiler trace of the window.

The last line of standard output is one JSON object (correct, attempted,
failed, metrics, device[, breakdown], checks); the numbers compared for
`correct` are also the last lines of standard error. Without a TPU, or on
a device missing from `bench/peaks.json`, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import (check, families, roofline, serve, trace,  # noqa: E402
                   traffic)

OUT = ROOT / "bench" / ".out"
CACHE = ROOT / ".jax_cache"


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a chip missing from the peaks."""


def load_benchmark(root: Path = ROOT) -> dict:
    """BENCHMARK.json at the root of the checkout."""
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    """A configuration by name, from the file BENCHMARK.json gives it; each
    model entry has to name a family that loads and hold its keys."""
    for c in bench["configs"]:
        if c["name"] == name:
            cfg = json.loads((root / c["file"]).read_text())
            for m in [cfg["target"]] + [d["model"] for d in cfg["drafters"]]:
                families.check_entry(m, c["file"])
            return cfg
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, workload: str, key: str):
    """The metrics of `key` ('end_to_end' or 'per_layer') a cell reports."""
    return [m for m in bench[key]
            if "workloads" not in m or workload in m["workloads"]]


def reader(name: str):
    """The per-layer metric reader `bench/metrics/<name>.py`."""
    return importlib.import_module(f"bench.metrics.{name}").read


def check_device(chips: int):
    """The cell's chips, as JAX reports them; fails without a TPU or with
    a device kind that the peak table does not hold."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {d.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"needs {chips} chips, JAX found {len(devs)}")
    try:
        peak = roofline.peaks(d.device_kind)
    except KeyError as e:
        raise NoDevice(str(e)) from e
    return d, peak


def enable_cache():
    """JAX's persistent compile cache at a fixed path in the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), keeping every program however
    fast it compiles and however small it is: after a cell's first run,
    set-up loads every program and compiles none."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ------------------------------------------------------------- metrics

def percentile(values, q: float) -> float:
    """The q-th percentile (nearest rank) of `values`; inf counts."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(0, min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1))
    return float(v[k])


def end_to_end(cell, w, setup_s: float) -> dict:
    """Client-side metrics of the window (and their sample counts)."""
    secs = w.t_end - w.t_open
    toks = sum(n for t in cell.timings.values() for (c, n) in t.commits
               if w.t_open <= c < w.t_end)
    done = [t for t in cell.timings.values()
            if t.req.done and t.last is not None
            and w.t_open <= t.last < w.t_end and t.n >= 2]
    tpot = [(t.last - t.first) * 1e3 / (t.n - 1) for t in done]
    due = [t for t in cell.timings.values() if w.t_open <= t.due < w.t_end]
    ttft = [((t.first - t.due) * 1e3 if t.first is not None
             else float("inf")) for t in due]
    return {
        "tokens_per_s": (toks / secs, "tokens/s", toks),
        "ttft_p90_ms": (percentile(ttft, 90), "ms", len(ttft)),
        "tpot_p50_ms": (percentile(tpot, 50), "ms", len(tpot)),
        "tpot_p90_ms": (percentile(tpot, 90), "ms", len(tpot)),
        "setup_s": (setup_s, "s", 1),
    }


def layer_context(cell, w, tr, peak) -> dict:
    """What the per-layer readers read."""
    recs = cell.eng.stats.records[w.n_records0:w.n_records1]
    commits = []
    for t in cell.timings.values():
        pos = len(t.req.prompt)
        for c, n in t.commits:
            if w.t_open <= c < w.t_end:
                commits.append((pos, n))
            pos += n
    n = sum(k for _, k in commits)
    mean_ctx = sum(p * k + k * (k - 1) / 2 for p, k in commits) / n if n else 0
    return {"records": recs, "window_s": w.t_end - w.t_open,
            "window_ms": (w.b_open, w.b_end),
            "mean_context": mean_ctx,
            "survived": w.survived, "invalidated": w.invalidated,
            "window_compiles": w.compiles, "trace": tr,
            "target": cell.cfg["target"], "peak": peak, "commits": commits}


# ------------------------------------------------------------- the run

def run_cell(cfg: dict, mix: dict, *, workload: str, seed: int,
             seconds: float, trace_on: bool, e2e: list, per_layer: list,
             peak: dict, control: bool = False,
             out_dir: Path = OUT, log=print,
             selector: trace.Selector = trace.TPU) -> dict:
    """Set up, warm up, measure, check: the result object of one run."""
    def phase(name):
        st = jax.devices()[0].memory_stats() or {}
        log(f"phase: {name} at {time.monotonic() - T_PROCESS:.3f} s, "
            f"bytes_in_use {st.get('bytes_in_use')}, peak "
            f"{st.get('peak_bytes_in_use')}, {serve.COUNTS}", file=sys.stderr,
            flush=True)

    cell = serve.Cell(cfg, mix, seed)
    phase("weights planted, engine built")
    gamma_max = int(cfg.get("cosine", {}).get("gamma_max", 16))
    parts = serve.sweep_shapes(cell, gamma_max)
    phase(f"shapes swept {parts}")
    n_steps = serve.warm_up(cell)
    phase(f"warm-up served in {n_steps} steps")
    reqs = cell.requests(int(mix["requests"]))
    trace_dir = None
    if trace_on:
        trace_dir = out_dir / f"trace-{workload}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    if mix["loop"] == "closed":
        cl = serve.fill_closed(cell, reqs, time.monotonic())
    else:
        # the schedule starts now; the window opens after the pre-roll,
        # with the system loaded
        cl = serve.Clients(cell, reqs, closed=False, start=time.monotonic())
        cl.serve_until(cl.start + float(mix["preroll_s"]))
    setup_s = time.monotonic() - T_PROCESS
    w = serve.measure(cell, cl, seconds, trace_dir)
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    cell.eng.backend.sync()
    e2e_all = end_to_end(cell, w, setup_s)
    due_in = [t for t in cell.timings.values() if w.t_open <= t.due < w.t_end]
    attempted = len(due_in)
    failed = sum(1 for t in due_in if t.first is None)
    tr = None
    breakdown = None
    if trace_on:
        phase("trace written")
        pd = trace.load(str(trace_dir))
        phase("trace loaded")
        red = trace.reduce(pd, selector)
        phase("trace reduced")
        tr = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = layer_context(cell, w, tr, peak)
    # the reference runs on the chip once the program's state is gone
    sample = check.sample(cell.timings, seed,
                          int(cfg["correct"]["sample_tokens"]),
                          int(cfg["correct"]["sample_requests"]))
    drafts = cell.drafts.entries
    length = int(cfg["serving"]["max_len"])
    cell.shutdown()
    del cell, cl
    gc.collect()
    phase("program state freed")
    gaps = check.compare(cfg, seed, sample, drafts, length, control=control)
    phase("reference compared")
    log(f"run: readings {json.dumps(gaps)}", file=sys.stderr, flush=True)
    # the control run is judged on the control's readings
    rows = check.verdict(check.control_readings(gaps) if control else gaps,
                         cfg["correct"])
    correct = all(r["ok"] for r in rows)
    metrics = {}
    if trace_on:
        for m in per_layer:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in e2e:
            v, unit, _ = e2e_all[m["name"]]
            metrics[m["name"]] = {"value": float(v), "unit": unit}
    for name, (v, unit, n) in e2e_all.items():
        log(f"run: {name} = {v} {unit} over {n} samples", file=sys.stderr)
    log(f"run: generator late by {w.late_s} s; window compiles "
        f"{w.compiles} ({w.compile_s} s)", file=sys.stderr)
    for r in rows:
        log(f"check: {r['name']} {r['value']} limit {r['limit']} "
            f"{'ok' if r['ok'] else 'FAIL'}", file=sys.stderr)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["readings"] = gaps
    result["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                        for r in rows}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the fp8 control at the same positions "
                         "and judge `correct` on the control's readings")
    args = ap.parse_args(argv)
    try:
        bench = load_benchmark()
        wl = find_workload(bench, args.workload)
        cfg = load_config(bench, wl["config"])
        mix = traffic.load_mix(wl["traffic"])
        _, peak = check_device(int(wl["chips"]))
    except (NoDevice, FileNotFoundError, KeyError,
            families.ConfigError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    log_cache = enable_cache()
    print(f"bench: compile cache {log_cache} "
          f"{jax.config.jax_enable_compilation_cache}", file=sys.stderr)
    result = run_cell(
        cfg, mix, workload=args.workload, seed=args.seed,
        seconds=args.seconds, trace_on=bool(args.trace),
        e2e=cell_metrics(bench, args.workload, "end_to_end"),
        per_layer=cell_metrics(bench, args.workload, "per_layer"),
        peak=peak, control=args.control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
