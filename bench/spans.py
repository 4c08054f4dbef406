#!/usr/bin/env python3
"""The program's host spans: read per verification from the window's
records, and laid over a profiler trace to put the device's idle time
down to the host work that held it.

The program names its host spans once (`repro.obs.HOST_SPANS`). Each
`IterationRecord` carries `host_ms`, the inclusive ms of every span since
the previous record, which the per-layer readers `draft_host_ms`,
`walk_host_ms`, `verify_wait_ms` and `commit_host_ms` read per
verification. In a `--trace 1` profile the same spans are host events on
the engine thread's line (the line of the benchmark's `step` spans) and
the verification server's; `idle_by_span` names each device-idle stretch
by the innermost span over it.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s>

serves one cell as `bench/run.py --trace 1` does (no reference check),
then prints on standard error the cost of a host region with no profiler
running, the device-idle seconds of the traced slice by innermost span
(`run: idle by span`), the engine thread's wall time by innermost span,
the share of the `step` spans' time the program's spans cover, the share
of idle under a program span, and the tokens/s of the window's untraced
part and of its traced slice; its last line of standard output is the
same as one JSON object.
"""
from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import program  # noqa: E402,F401  (puts the program on the path)
from bench.trace import HOST_SPANS as BENCH_SPANS  # noqa: E402
from bench.trace import Interval, clip, merge  # noqa: E402

try:
    from repro.obs import HOST_SPANS as PROGRAM_SPANS
except ImportError:     # a program without host spans
    PROGRAM_SPANS = ()


# ----------------------------------------------------------- records

def per_verification(ctx, spans) -> float | None:
    """Inclusive ms of `spans` per verification in the window, from the
    records' `host_ms`; None where no record carries any."""
    recs = ctx["records"]
    maps = [getattr(r, "host_ms", None) for r in recs]
    if not recs or not any(maps):
        return None
    return sum(m.get(s, 0.0) for m in maps if m for s in spans) / len(recs)


# ------------------------------------------------------------- trace

def thread_lines(pd, pred, names) -> List[List[Interval]]:
    """The events named in `names` on each selected line of a trace, one
    list per line (a line is one thread; threads may share a name)."""
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            if not pred(plane.name, line.name):
                continue
            ev = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                   e.name) for e in line.events
                  if e.duration_ns > 0 and e.name in names]
            if ev:
                out.append(ev)
    return out


def tiling(spans: List[Interval], lo: float, hi: float) -> List[Interval]:
    """[lo, hi] cut into pieces, each named by the innermost of `spans`
    (one thread's events, so they nest) over it, or 'none'."""
    out: List[Interval] = []
    stack: List[Interval] = []
    t = lo

    def advance(to):
        nonlocal t
        while t < to:
            while stack and stack[-1][1] <= t:
                stack.pop()
            end = min(to, stack[-1][1]) if stack else to
            out.append((t, end, stack[-1][2] if stack else "none"))
            t = end

    for s in sorted(clip(spans, lo, hi), key=lambda x: (x[0], -x[1])):
        advance(s[0])
        stack.append(s)
    advance(hi)
    return out


def idle(ops: List[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] with no device operation."""
    gaps, t = [], lo
    for a, b in merge(clip(ops, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def by_label(pieces: List[Interval], within=None) -> Dict[str, float]:
    """Seconds of each label, inside the sorted disjoint `within`
    stretches (all of each piece when None)."""
    out: Dict[str, float] = {}
    if within is None:
        for a, b, k in pieces:
            out[k] = out.get(k, 0.0) + (b - a) * 1e-9
        return out
    i = 0
    for a, b in within:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, k = pieces[j]
            d = min(e, b) - max(s, a)
            if d > 0:
                out[k] = out.get(k, 0.0) + d * 1e-9
            j += 1
    return out


def idle_by_span(ops, spans, lo, hi) -> Dict[str, float]:
    """Device-idle seconds of [lo, hi] by the innermost span over them."""
    return by_label(tiling(spans, lo, hi), idle(ops, lo, hi))


def coverage(spans, names, within: str, lo: float, hi: float) -> float:
    """Share of the time of the `within` spans in [lo, hi] that spans
    named in `names` cover."""
    outer = merge([s for s in clip(spans, lo, hi) if s[2] == within])
    inner = [s for s in spans if s[2] in names]
    total = sum(b - a for a, b in outer)
    if total <= 0:
        return 0.0
    covered = sum(b - a for a0, b0 in outer
                  for a, b in merge(clip(inner, a0, b0)))
    return covered / total


def named_share(by_span: Dict[str, float], names) -> float:
    """Share of the seconds in `by_span` under one of `names`."""
    total = sum(by_span.values())
    return sum(v for k, v in by_span.items() if k in names) / total \
        if total > 0 else 0.0


# --------------------------------------------------------- the run

def region_ns(n: int = 200_000) -> float:
    """ns per host region (enter and exit, counted into a registry) with
    no profiler running."""
    import time

    from repro.obs import MetricsRegistry, Tracer
    tr = Tracer(metrics=MetricsRegistry())
    t = time.perf_counter()
    for _ in range(n):
        with tr.region("engine.walk", cohort=1):
            pass
    return (time.perf_counter() - t) / n * 1e9


def attribute(cfg: dict, mix: dict, *, workload: str, seed: int,
              seconds: float, peak: dict, selector=None) -> dict:
    """Serve one cell with the window's end traced (as `bench/run.py
    --trace 1` does, without the reference) and read the host spans."""
    import shutil
    import time

    from bench import run, serve, trace

    sel = selector or trace.TPU
    out = {"region_ns_profiler_off": region_ns()}
    cell = serve.Cell(cfg, mix, seed)
    serve.sweep_shapes(cell, int(cfg.get("cosine", {}).get("gamma_max", 16)))
    serve.warm_up(cell)
    reqs = cell.requests(int(mix["requests"]))
    if mix["loop"] == "closed":
        cl = serve.fill_closed(cell, reqs, time.monotonic())
    else:
        cl = serve.Clients(cell, reqs, closed=False, start=time.monotonic())
        cl.serve_until(cl.start + float(mix["preroll_s"]))
    trace_dir = run.OUT / f"spans-{workload}-{seed}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    w = serve.measure(cell, cl, seconds, trace_dir)
    cell.eng.backend.sync()

    t_traced = w.t_end - min(serve.TRACED_S, seconds)
    toks = [(c, n) for t in cell.timings.values() for c, n in t.commits]
    for key, a, b in (("tokens_per_s_untraced", w.t_open, t_traced),
                      ("tokens_per_s_traced", t_traced, w.t_end)):
        out[key] = sum(n for c, n in toks if a <= c < b) / (b - a) \
            if b > a else None
    ctx = run.layer_context(cell, w, None, peak)
    for m in ("draft_host_ms", "walk_host_ms", "verify_wait_ms",
              "commit_host_ms"):
        out[m] = run.reader(m)(ctx)
    recs = ctx["records"]
    per_rec: Dict[str, float] = {}
    for r in recs:
        for k, v in getattr(r, "host_ms", {}).items():
            per_rec[k] = per_rec.get(k, 0.0) + v / len(recs)
    out["host_ms_per_verification"] = per_rec

    pd = trace.load(str(trace_dir))
    lines = thread_lines(pd, sel.host, set(PROGRAM_SPANS) | set(BENCH_SPANS))
    # the engine thread's line holds the benchmark's 'window' and 'step'
    host = next(ev for ev in lines if trace.window_bounds(ev) is not None)
    lo, hi = trace.window_bounds(host)
    by_span = idle_by_span(trace.events(pd, sel.ops), host, lo, hi)
    out["window_s"] = (hi - lo) * 1e-9
    out["idle_s"] = sum(by_span.values())
    out["idle_by_span"] = dict(sorted(by_span.items(), key=lambda kv: -kv[1]))
    out["wall_by_span"] = dict(sorted(by_label(tiling(host, lo, hi)).items(),
                                      key=lambda kv: -kv[1]))
    out["step_covered_share"] = coverage(host, set(PROGRAM_SPANS), "step",
                                         lo, hi)
    out["idle_named_share"] = named_share(by_span, set(PROGRAM_SPANS))
    server = [s for ev in lines if ev is not host for s in ev]
    out["server_s"] = by_label(clip(server, lo, hi))
    cell.shutdown()
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out


def main(argv=None) -> int:
    import argparse
    import json

    from bench import run, traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    wl = run.find_workload(bench, args.workload)
    cfg = run.load_config(bench, wl["config"])
    mix = traffic.load_mix(wl["traffic"])
    try:
        _, peak = run.check_device(int(wl["chips"]))
    except run.NoDevice as e:
        print(f"spans: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    out = attribute(cfg, mix, workload=args.workload, seed=args.seed,
                    seconds=args.seconds, peak=peak)
    for k, v in out.items():
        print(f"run: {k.replace('_', ' ')} {json.dumps(v)}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
