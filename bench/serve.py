"""Set-up and the measured window of one run: plant the weights, build the
engine, warm up the cell's shapes, then drive the served path with the
cell's traffic through the public `submit` / `step` / `on_commit` calls
and time every request on the client's clock."""
from __future__ import annotations

import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import agreement, traffic
from bench.program import build_engine


# process-wide counts of compiles and persistent-cache lookups, for the
# set-up log
COUNTS = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
          "cache_misses": 0}


def _count_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        COUNTS["compiles"] += 1
        COUNTS["compile_s"] = round(COUNTS["compile_s"] + duration, 3)


def _count_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        COUNTS["cache_hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        COUNTS["cache_misses"] += 1


jax.monitoring.register_event_duration_secs_listener(_count_duration)
jax.monitoring.register_event_listener(_count_event)


@contextmanager
def counting_compiles():
    """Programs built inside the block (count, seconds): JAX's backend-
    compile event covers a compile and a load from the persistent cache
    alike. Each is also logged (name and shapes) to standard error."""
    acc = {"n": 0, "s": 0.0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            acc["n"] += 1
            acc["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    log_compiles = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    try:
        yield acc
    finally:
        jax.config.update("jax_log_compiles", log_compiles)
        jax.monitoring.unregister_event_duration_listener(listen)


def annotate(name: str):
    """A host span in the profiler's trace (no cost when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


@dataclass
class Timing:
    """One request on the client's clock (seconds, monotonic)."""
    req: object
    due: float
    first: Optional[float] = None
    last: Optional[float] = None
    n: int = 0
    commits: List[tuple] = field(default_factory=list)


@dataclass
class DraftLog:
    """Drafter proposals as the router receives them, one entry per
    request and verification: the committed length before it, every
    participant's proposals and the committed tokens."""
    entries: List[tuple] = field(default_factory=list)

    def wrap(self, eng, by_rid):
        update = eng.router.update

        def recording(rid, d_toks, d_confs, toks, parts):
            if rid in by_rid:
                r = by_rid[rid].req
                base = len(r.prompt) + len(r.generated)
                self.entries.append((rid, base, {int(p): [int(t) for t in
                                                          d_toks[p]]
                                                 for p in parts},
                                     [int(t) for t in toks]))
            return update(rid, d_toks, d_confs, toks, parts)

        eng.router.update = recording


class Cell:
    """One cell's served system and its traffic, for one seed."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.models = [cfg["target"]] + agreement.drafter_list(cfg)
        canon, tables = agreement.plant_all(seed, cfg)
        jax.block_until_ready(canon)
        self.succ = np.asarray(tables["succ"])
        self.slices = [tuple(s) for s in cfg["agreement"]["domains"]]
        self.eng = build_engine(cfg, self.models, canon, seed)
        del canon
        self.slots = int(cfg["serving"]["live_slots"])
        self.timings: Dict[int, Timing] = {}
        self.drafts = DraftLog()
        self.drafts.wrap(self.eng, self.timings)
        self.eng.on_commit = self._on_commit

    # ---------------------------------------------------------- traffic
    def domains(self) -> List[int]:
        """Domain indices this cell's requests come from."""
        nd = len(agreement.drafter_list(self.cfg))
        if self.mix["domains"] == "drafters":
            return list(range(nd))
        return [len(self.slices) - 1]

    def requests(self, n: int, seed_offset: int = 0):
        """The first n requests of the mix for this seed (or a warm-up
        stream at another seed)."""
        return traffic.schedule(
            self.mix, seed=self.seed + seed_offset, n_requests=n,
            domains=self.domains(), succ=self.succ, slices=self.slices)

    # ---------------------------------------------------------- engine
    def _on_commit(self, req, toks, _now_ms):
        t = time.monotonic()
        tm = self.timings.get(req.rid)
        if tm is None:
            return
        if tm.first is None:
            tm.first = t
        tm.last = t
        tm.n += len(toks)
        tm.commits.append((t, len(toks)))

    def submit(self, r: traffic.Request, due: float, arrival_ms: float):
        """Hand one request to the engine; `due` on the client's clock."""
        with annotate("submit"):
            req = self.eng.submit(r.prompt, max_new_tokens=r.max_new,
                                  arrival_ms=arrival_ms)
        self.timings[req.rid] = Timing(req, due)
        return req

    def step(self):
        with annotate("step"):
            return self.eng.step()

    def backend_ms(self) -> float:
        return self.eng.backend.now_ms()

    def shutdown(self):
        self.eng.backend.shutdown()


@dataclass
class Window:
    """What the measured window saw, on the client's clock."""
    t_open: float
    t_end: float
    n_records0: int = 0
    n_records1: int = 0
    survived: int = 0
    invalidated: int = 0
    compiles: int = 0           # programs compiled or loaded from the cache
    compile_s: float = 0.0
    b_open: float = 0.0         # the window on the backend's clock (ms)
    b_end: float = 0.0
    late_s: float = 0.0         # how late the loop handed requests over


class Clients:
    """The load generator. Requests become due (open loop: on the mix's
    schedule; closed loop: when a client's previous request completes)
    and wait in the client's queue; the generator hands the engine the
    oldest due request whenever fewer than `slots` are outstanding, one
    per engine step. Every request is timed from when it was due, so
    the queue's wait counts in its latency.

    The bound and the pacing keep the program inside one chip: its engine
    prefills every cold request at once, materialising full-vocabulary
    float32 logits for each, and grows the slot pool (doubling every
    cache) when more requests are live than it has slots."""

    def __init__(self, cell: Cell, reqs, closed: bool, start: float):
        self.cell, self.closed, self.start = cell, closed, start
        self.todo = list(reqs)
        self.ready: List[tuple] = []          # (due, request)
        self.done_seen = set()
        self.late: List[float] = []

    def _outstanding(self) -> int:
        return sum(1 for t in self.cell.timings.values() if not t.req.done)

    def poll(self, now: float, accept: bool = True) -> None:
        """Move due requests to the queue and hand one over."""
        cell = self.cell
        if self.closed:
            for rid, t in list(cell.timings.items()):
                if t.req.done and rid not in self.done_seen:
                    self.done_seen.add(rid)
                    if accept and self.todo:
                        self.ready.append((now, self.todo.pop(0)))
        else:
            while (accept and self.todo
                   and self.start + self.todo[0].due_ms / 1e3 <= now):
                r = self.todo.pop(0)
                self.ready.append((self.start + r.due_ms / 1e3, r))
        if self.ready and self._outstanding() < cell.slots:
            due, r = self.ready.pop(0)
            self.late.append(now - due)
            cell.submit(r, due, cell.backend_ms())

    def next_due(self) -> Optional[float]:
        if self.closed or not self.todo:
            return None
        return self.start + self.todo[0].due_ms / 1e3

    def serve_until(self, t_stop: float, accept: bool = True) -> None:
        """Step the engine, feeding it, until `t_stop` on the client clock."""
        while True:
            now = time.monotonic()
            if now >= t_stop:
                return
            self.poll(now, accept)
            if self.cell.step() is None:
                nd = self.next_due() if accept else None
                if not self.ready and nd is None and not self.closed:
                    return
                if nd is not None and nd > now:
                    time.sleep(min(nd, t_stop) - now)


def fill_closed(cell: Cell, reqs, start: float) -> Clients:
    """Start a closed loop: one client per live slot, the first requests
    handed over one per engine step."""
    cl = Clients(cell, reqs, closed=True, start=start)
    for _ in range(cell.slots):
        cl.ready.append((time.monotonic(), cl.todo.pop(0)))
        cl.poll(time.monotonic())
        cell.step()
    return cl


# seconds at the end of the window that a traced run records: writing a
# trace out takes several seconds per traced second on the chip (a whole
# 51 s window took 185 s), so a run that traced all of it would not end
# in its time
TRACED_S = 10.0


def measure(cell: Cell, cl: Clients, seconds: float, trace_dir=None,
            wait_s: float = 60.0) -> Window:
    """The measured window: serve for `seconds`, then (taking no new
    requests) serve on until every request due in the window has its
    first token, for at most `wait_s`. With `trace_dir`, the profiler
    records the window's last `TRACED_S` seconds, which the 'window' host
    span marks."""
    ex = cell.eng.executor
    with counting_compiles() as comp:
        t = time.monotonic()
        w = Window(t, t + seconds, len(cell.eng.stats.records))
        w.b_open = cell.backend_ms()
        w.b_end = w.b_open + seconds * 1e3
        n_late = len(cl.late)
        s0, i0 = ex.n_survived, ex.n_invalidated
        if trace_dir is not None:
            cl.serve_until(w.t_end - min(TRACED_S, seconds))
            # the benchmark's own spans are at host level 1; the runtime's
            # many level-2 host events, and the HLO of every program
            # loaded, would only slow the trace's writing and reading
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with annotate("window"):
            cl.serve_until(w.t_end)
        w.n_records1 = len(cell.eng.stats.records)
        w.survived, w.invalidated = ex.n_survived - s0, ex.n_invalidated - i0
        if trace_dir is not None:
            jax.profiler.stop_trace()
    w.compiles, w.compile_s = comp["n"], comp["s"]
    w.late_s = max(cl.late[n_late:], default=0.0)
    due_in = [t for t in cell.timings.values() if w.t_open <= t.due < w.t_end]
    limit = time.monotonic() + wait_s
    while any(t.first is None for t in due_in) and time.monotonic() < limit:
        cl.poll(time.monotonic(), accept=False)
        if cell.step() is None and not cl.ready:
            break
    return w


def warm_up(cell: Cell, new_tokens: int = 24):
    """Serve one block of the cell's own mix (a stream at another seed,
    so every prompt length the window meets; outputs cut to `new_tokens`)
    as a closed loop over every live slot, then drain: the prefill and
    step programs of the window's shapes compile (or load from the
    persistent cache) here, in set-up."""
    reqs = cell.requests(int(cell.mix["block"]), seed_offset=7919)
    for r in reqs:
        r.max_new = min(r.max_new, new_tokens)
    cl = fill_closed(cell, reqs, time.monotonic())
    steps = 0
    while cl.todo or cl.ready or cl._outstanding():
        cl.poll(time.monotonic())
        cell.step()
        steps += 1
    cell.eng.backend.sync()
    cell.timings.clear()
    cell.drafts.entries.clear()
    return steps


def sweep_shapes(cell: Cell, gamma_max: int):
    """Compile (or load from the cache) the step programs the window's
    cohorts need, through the backend's own calls on dummy requests, at
    every batch size from 1 to the live slots (the runner pads rows to
    buckets, but the host's slices of each result are taken at the real
    batch size): tree verification of 1 .. 2 * (gamma_max + 1) nodes (a
    fused chain of up to gamma_max + 1 tokens with one side branch per
    depth), drafter snapshot, teacher-forced extend of 1 .. gamma_max + 2
    tokens and decode, and commits of 1 .. gamma_max + 2 tokens (an
    accepted chain and its correction) at every bucket of rows, whose
    shape alone a commit's programs depend on. Prefill shapes come from
    serving the mix itself (`warm_up`). The dummy requests are dropped
    afterwards."""
    be = cell.eng.backend
    n = cell.slots
    base = 1 << 40
    rids = [base + i for i in range(n)]
    ctx = [1] * 8
    for rid in rids:
        be.prefill_target({rid: ctx})
        be.prefill_drafters({rid: ctx[:-1]}, batched=False)
    sizes = range(1, n + 1)
    t0 = time.monotonic()
    for b in sizes:
        for g in range(1, 2 * (gamma_max + 1) + 1):
            toks = np.zeros((b, g), np.int32)
            rel = np.broadcast_to(np.arange(g, dtype=np.int32), (b, g))
            mask = np.broadcast_to(np.tril(np.ones((g, g), bool)), (b, g, g))
            be.verify_dispatch(rids[:b], toks, rel, mask).result()
    t_verify, t0 = time.monotonic() - t0, time.monotonic()
    for b in sizes:
        for di in range(len(be.drafters)):
            snap = be.draft_snapshot(di, rids[:b])
            for t in range(1, gamma_max + 3):
                snap = be.draft_extend(di, snap, np.zeros((b, t), np.int32))
            lg, _ = be.draft_decode(di, rids[:b], np.zeros(b, np.int32), snap)
            # the engine's per-step reading of the drafters' logits
            probs = jax.nn.softmax(jnp.asarray(lg), -1)
            tok = np.asarray(jnp.argmax(probs, -1))
            np.asarray(jnp.take_along_axis(probs, jnp.asarray(tok)[:, None],
                                           -1))
    t_draft, t0 = time.monotonic() - t0, time.monotonic()
    # a commit's programs and slices are taken at the bucket's rows
    for b in [b for b in (1, 2, 4, 8, 16, 32, 64, 128) if b < n] + [n]:
        for k in range(1, gamma_max + 3):
            be.commit_target({rid: [0] * k for rid in rids[:b]})
            be.commit_drafters({rid: [0] * k for rid in rids[:b]})
    be.sync()
    for rid in rids:
        be.drop_request(rid)
    be.sync()
    return {"verify_s": t_verify, "draft_s": t_draft,
            "commit_s": time.monotonic() - t0}
