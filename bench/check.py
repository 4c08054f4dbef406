"""The comparison that decides `correct`.

After the window has closed and the program's state is freed, a sample of
the finished requests (drawn from the seed, the longest always in it) is
run through the plain reference (`bench/reference.py`) with weights drawn
anew from the seed:

* target: at every served position, the gap by which the served token's
  logit lies below the reference's best (0 where they agree). This covers
  the target's prefill, tree verify and commit extend, and the engine's
  tree build, fusion, acceptance walk and commit: a token altered
  anywhere on that path shows as a gap far above rounding.
* drafters: every proposal the router was shown whose context is a
  prefix of the served stream (all participants agreed with the
  committed tokens before it), against that drafter's reference. This
  covers the drafters' prefill and decode.

Each number is the widest gap over the sample; each has its own limit in
the configuration file (`correct`), and each count of positions compared
must be at least 1. With `control`, the same positions are also read with
the fp8 control in the program's place (`control_readings`), and the
verdict is taken on those.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import agreement, reference


def sample(timings, seed: int, min_tokens: int, max_requests: int):
    """Finished requests: the longest, then others in a seeded order until
    `min_tokens` served tokens or `max_requests` requests."""
    done = [t.req for t in timings.values() if t.req.done and t.req.generated]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.generated), r.rid))
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    rest = [done[i] for i in rng.permutation(len(done) - 1) + 1] \
        if len(done) > 1 else []
    out, n = [done[0]], len(done[0].generated)
    for r in rest:
        if n >= min_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.generated)
    return out


def draft_positions(entries, rids) -> Dict[int, Dict[int, Dict[int, int]]]:
    """{node: {rid: {position: proposed token}}} for every proposal whose
    context lies on the served stream. `position` indexes the token it
    proposes (prompt + generated)."""
    out: Dict[int, Dict[int, Dict[int, int]]] = {}
    for rid, base, props, committed in entries:
        if rid not in rids:
            continue
        width = max(len(p) for p in props.values())
        for i in range(width):
            if i > len(committed) or any(
                    p[:i] != committed[:i] for p in props.values()):
                break
            for node, p in props.items():
                if i < len(p):
                    out.setdefault(node, {}).setdefault(rid, {}).setdefault(
                        base + i, p[i])
    return out


def compare(cfg: dict, seed: int, requests, drafts, length: int,
            control: bool = False) -> dict:
    """Widest gaps of the target and of the drafters, with the counts of
    positions compared (and the control's gaps with `control`)."""
    models = [cfg["target"]] + agreement.drafter_list(cfg)
    canon, _ = agreement.plant_all(seed, cfg)
    rids = {r.rid: r for r in requests}
    out = {"target_gap": 0.0, "target_positions": 0, "drafter_gap": 0.0,
           "drafter_positions": 0, "requests": len(requests)}
    if control:
        out.update(target_control_gap=0.0, drafter_control_gap=0.0)
    seqs = {r.rid: list(r.prompt) + [int(t) for t in r.generated]
            for r in requests}
    for r in requests:
        seq, p0 = seqs[r.rid], len(r.prompt)
        toks = seq + [0] * (length - len(seq))
        pick = seq[1:] + [0] * (length - len(seq) + 1)
        res = reference.readings(canon[0], models[0], toks, pick, control)
        sl = slice(p0 - 1, len(seq) - 1)
        gap = res["best"][sl] - res["picked"][sl]
        out["target_gap"] = max(out["target_gap"], float(np.max(gap)))
        out["target_positions"] += len(gap)
        if control:
            out["target_control_gap"] = max(out["target_control_gap"], float(
                np.max(res["control_gap"][sl])))
    for node, per_rid in draft_positions(drafts, set(rids)).items():
        for rid, pos in per_rid.items():
            seq = seqs[rid]
            pos = {p: t for p, t in pos.items() if 1 <= p < len(seq)}
            if not pos:
                continue
            toks = seq + [0] * (length - len(seq))
            pick = [0] * length
            for p, t in pos.items():
                pick[p - 1] = t
            res = reference.readings(canon[1 + node], models[1 + node], toks,
                                     pick, control)
            idx = np.asarray(sorted(pos)) - 1
            gap = res["best"][idx] - res["picked"][idx]
            out["drafter_gap"] = max(out["drafter_gap"], float(np.max(gap)))
            out["drafter_positions"] += len(idx)
            if control:
                out["drafter_control_gap"] = max(
                    out["drafter_control_gap"],
                    float(np.max(res["control_gap"][idx])))
    return out


def control_readings(gaps: dict) -> dict:
    """The control's gaps in the program's place, for the verdict."""
    return dict(gaps, target_gap=gaps["target_control_gap"],
                drafter_gap=gaps["drafter_control_gap"])


def verdict(gaps: dict, limits: dict) -> List[dict]:
    """Each compared number beside its limit."""
    rows = [{"name": k, "value": gaps[k], "limit": float(limits[k]),
             "ok": gaps[k] <= float(limits[k])}
            for k in ("target_gap", "drafter_gap")]
    rows += [{"name": k, "value": gaps[k], "limit": 1, "ok": gaps[k] >= 1}
             for k in ("target_positions", "drafter_positions")]
    return rows
