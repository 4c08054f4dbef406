"""One general generator for every traffic mix (`bench/traffic/<mix>.json`).

A mix is data: the loop (closed: a client per live slot sends its next
request when the last completes; open: arrivals on a fixed schedule), the
prompt and output length distributions (lognormal, clipped), the arrival
rate and bursts of an open loop, and which vocabulary domains requests
come from ("drafters": evenly over the drafters' domains; "other": the
domain no drafter knows).

Every seed gets the same work in another order. A block holds fixed
request shapes (prompt and output lengths at fixed quantiles of their
distributions, paired in a fixed order, and domains in turn) and fixed
inter-arrival gaps; the seed permutes each block. So any whole number of blocks holds
the same multiset of sizes for every seed, and a closed loop that serves
k blocks in its window has done the same work whatever the seed. The
seed also picks the prompts' tokens: a walk along the target's planted
successor map from a random token of the request's domain, with a share
of random tokens of that domain mixed in.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent


@dataclass
class Request:
    """One request of the schedule: due time (ms after the window opens;
    None in a closed loop), domain, prompt tokens and output length."""
    index: int
    due_ms: Optional[float]
    domain: int
    prompt: List[int]
    max_new: int


def load_mix(name: str) -> dict:
    """A traffic mix's parameters by name."""
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def quantile_lengths(spec: dict, n: int):
    """n lengths at the mid-quantiles of a lognormal (median, sigma),
    clipped to [min, max]."""
    nd = NormalDist()
    hi = int(spec["max"])
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = float(spec["median"]) * math.exp(float(spec["sigma"]) * z)
        out.append(int(min(max(round(v), int(spec["min"])), hi)))
    return out


def _blocks(values, n_blocks, rng):
    """`values` repeated n_blocks times, each copy in its own order."""
    out = []
    for _ in range(n_blocks):
        out.extend(rng.permutation(np.asarray(values)).tolist())
    return out


def schedule(mix: dict, *, seed: int, n_requests: int, domains: List[int],
             succ: np.ndarray, slices) -> List[Request]:
    """The first `n_requests` requests of a mix for one seed (rounded up
    to whole blocks). `domains` are the domain indices requests come
    from; `succ` the planted successor of every token; `slices` the
    [lo, hi) of every domain."""
    block = int(mix["block"])
    n_blocks = -(-n_requests // block)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    # one block of request shapes, paired in a fixed (seed-free) order
    pair = np.random.default_rng(0).permutation(block)
    outs = quantile_lengths(mix["output"], block)
    shapes = [(p, outs[pair[i]], domains[i % len(domains)])
              for i, p in enumerate(quantile_lengths(mix["prompt"], block))]
    order = _blocks(list(range(block)), n_blocks, rng)
    prompts = [shapes[i][0] for i in order]
    outputs = [shapes[i][1] for i in order]
    doms = [shapes[i][2] for i in order]
    due = [None] * (n_blocks * block)
    if mix["loop"] == "open":
        due = open_arrivals(mix, float(mix["rate_per_s"]), n_blocks, block,
                            rng)
    share = float(mix["random_token_share"])
    reqs = []
    for i in range(n_blocks * block):
        lo, hi = slices[doms[i]]
        t = int(rng.integers(lo, hi))
        toks = [t]
        for _ in range(prompts[i] - 1):
            t = (int(rng.integers(lo, hi)) if rng.random() < share
                 else int(succ[t]))
            toks.append(t)
        reqs.append(Request(i, due[i], int(doms[i]), toks, int(outputs[i])))
    return reqs


def open_arrivals(mix: dict, rate: float, n_blocks: int, block: int, rng):
    """Due times (ms) of an open loop at `rate` requests per second. A
    block's gaps are the mid-quantiles of an exponential with the mix's
    mean gap; every `burst_every`-th gap is replaced by `burst_size` - 1
    arrivals at the same instant (the burst's requests count toward the
    rate), so the mean rate holds and bursts stress prefill."""
    mean_ms = 1e3 / rate
    every = int(mix.get("burst_every", 0))
    size = int(mix.get("burst_size", 1))
    # of every `every` arrivals, `size` come at once: the other gaps
    # stretch so that the block still lasts block * mean_ms
    n_gaps = block
    if every and size > 1:
        n_bursty = block // every * (size - 1)
        n_gaps = block - n_bursty
    stretch = block / n_gaps
    gaps = [-mean_ms * stretch * math.log(1.0 - (i + 0.5) / n_gaps)
            for i in range(n_gaps)]
    out, t = [], 0.0
    for _ in range(n_blocks):
        g = rng.permutation(np.asarray(gaps)).tolist()
        j = 0
        for i in range(block):
            in_burst = every and size > 1 and (i % every) >= every - size + 1
            if not in_burst:
                t += g[j]
                j += 1
            out.append(t)
    return out
