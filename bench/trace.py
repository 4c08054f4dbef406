"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics.

Device busy time is the union of the intervals in which an operation ran
on the device; idle is the rest of the traced window. Per-program device
time sums the program-level events by their jit name. Idle gaps are
labelled by the benchmark's own host spans (`submit`, `step`, ...) that
cover them. Which planes and lines hold device operations, programs and
host spans is passed in, so the same code reads a TPU trace and the small
CPU trace its test keeps.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[float, float, str]     # (start ns, end ns, name)


@dataclass(frozen=True)
class Selector:
    """Which (plane, line) pairs of a trace hold what."""
    ops: Callable[[str, str], bool]        # device operations (busy time)
    programs: Callable[[str, str], bool]   # whole programs (jit names)
    host: Callable[[str, str], bool]       # the benchmark's host spans


TPU = Selector(
    ops=lambda p, ln: p.startswith("/device:TPU") and ln == "XLA Ops",
    programs=lambda p, ln: p.startswith("/device:TPU") and ln == "XLA Modules",
    host=lambda p, ln: p.startswith("/host:"))

HOST_SPANS = ("submit", "step", "on_commit", "window")


def load(log_dir: str):
    """The newest `.xplane.pb` under a profiler log directory."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    return ProfileData.from_file(paths[-1])


def events(pd, pred, names=None) -> List[Interval]:
    """Every event with a duration on the selected lines (only those
    named in `names`, when given)."""
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            if not pred(plane.name, line.name):
                continue
            for e in line.events:
                if e.duration_ns > 0 and (names is None or e.name in names):
                    out.append((float(e.start_ns),
                                float(e.start_ns + e.duration_ns), e.name))
    return out


def merge(iv: List[Interval]) -> List[Tuple[float, float]]:
    """Union of intervals as sorted disjoint (start, end) pairs."""
    out: List[List[float]] = []
    for s, e, _ in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(spans, lo: float, hi: float):
    """Intersect (start, end, ...) spans with [lo, hi]."""
    out = []
    for s in spans:
        a, b = max(s[0], lo), min(s[1], hi)
        if b > a:
            out.append((a, b) + tuple(s[2:]))
    return out


def busy_ns(ops: List[Interval], lo: float, hi: float) -> float:
    """Length of the union of device operations inside [lo, hi]."""
    return sum(b - a for a, b in merge(clip(ops, lo, hi)))


def program_name(event_name: str) -> str:
    """A program event's jit name without the module id or suffixes:
    'jit_slot_verify_chunk(123)' -> 'slot_verify_chunk'."""
    n = re.sub(r"\(.*$", "", event_name)
    n = re.sub(r"\.\d+$", "", n)
    return n[4:] if n.startswith("jit_") else n


def program_times(progs: List[Interval], lo: float, hi: float
                  ) -> Dict[str, Tuple[float, int]]:
    """{program: (device seconds, calls)} of programs that start in
    [lo, hi]."""
    out: Dict[str, Tuple[float, int]] = {}
    for s, e, name in progs:
        if lo <= s < hi:
            k = program_name(name)
            t, n = out.get(k, (0.0, 0))
            out[k] = (t + (e - s) * 1e-9, n + 1)
    return out


def op_times(ops: List[Interval], lo: float, hi: float, top: int = 10,
             width: int = 120):
    """The `top` device operations by total seconds in [lo, hi], named by
    the first `width` characters of their HLO text."""
    acc: Dict[str, float] = {}
    for s, e, name in clip(ops, lo, hi):
        name = name[:width]
        acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
    return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])[
        :top]


def idle_gaps(ops: List[Interval], host: List[Interval], lo: float,
              hi: float, top: int = 10):
    """The `top` longest gaps in [lo, hi] with no device operation, each
    named by the innermost host span covering its middle ('none' where
    no benchmark span does)."""
    busy = merge(clip(ops, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    named = [h for h in host if h[2] in HOST_SPANS]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (a + b)
        cover = [h for h in named if h[0] <= mid <= h[1]]
        label = min(cover, key=lambda h: h[1] - h[0])[2] if cover else "none"
        out.append([label, (b - a) * 1e-9])
    return out


def window_bounds(host: List[Interval]) -> Optional[Tuple[float, float]]:
    """The traced window: the benchmark's 'window' host span."""
    w = [h for h in host if h[2] == "window"]
    if not w:
        return None
    return w[0][0], w[0][1]


def reduce(pd, sel: Selector) -> dict:
    """Busy and idle time, per-program device time and the breakdown of a
    trace whose window is marked by the benchmark's 'window' span."""
    # the benchmark's spans are on the main thread's line: read that one
    # when it holds them, not every runtime thread's events
    main = lambda p, ln: sel.host(p, ln) and ln.startswith(("main", "python"))
    host = events(pd, main, HOST_SPANS)
    if window_bounds(host) is None:
        host = events(pd, sel.host, HOST_SPANS)
    ops, progs = events(pd, sel.ops), events(pd, sel.programs)
    bounds = window_bounds(host)
    if bounds is None:
        raise ValueError("the trace has no 'window' span")
    lo, hi = bounds
    busy = busy_ns(ops, lo, hi)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "programs": program_times(progs, lo, hi),
            "device_ops": op_times(ops, lo, hi),
            "idle_gaps": idle_gaps(ops, host, lo, hi)}
