#!/usr/bin/env python3
"""Readings for the limits of `correct`: one cell run on many seeds in one
process, so that set-up compiles or loads each program once.

    python bench/readings.py --workload <cell> --seconds <s> --control \
        --seeds <n> [<n> ...]

Each seed runs the whole of `bench/run.py`'s run (weights planted from
the seed, set-up, the measured window, the check) and prints one JSON
line: the seed, `correct` and the readings, the program's and, with
`--control`, the fp8 control's at the same positions. The timings of a
run after the first are not those of a fresh process and are not
reported. Like `bench/run.py`, it needs a TPU that the peak table holds.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import families, run, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        bench = run.load_benchmark()
        wl = run.find_workload(bench, args.workload)
        cfg = run.load_config(bench, wl["config"])
        mix = traffic.load_mix(wl["traffic"])
        _, peak = run.check_device(int(wl["chips"]))
    except (run.NoDevice, FileNotFoundError, KeyError,
            families.ConfigError) as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    for seed in args.seeds:
        res = run.run_cell(cfg, mix, workload=args.workload, seed=seed,
                           seconds=args.seconds, trace_on=False, e2e=[],
                           per_layer=[], peak=peak, control=args.control)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "readings": res.get("readings"),
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
