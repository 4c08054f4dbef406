"""Per-request / per-stage span tracer (DESIGN.md §2.6).

A `Span` is one closed interval on one *track* of the serving timeline:

  * stage tracks  — ``verify``, ``draft{i}`` (one per drafter node),
    ``draft`` (the coupled baselines' aggregate cluster), ``cluster``
    (fusion/transit activity that is not node occupancy). Work spans on a
    serial stage track tile without overlap; measured idle gaps are
    emitted as explicit ``bubble`` spans carrying their cause, so the
    stage's busy/idle totals are recoverable from the trace alone (and
    must match `ServeStats` — CI gates the drift).
  * request tracks — ``req{rid}``: lifecycle instants (``arrival``,
    ``shed``, ``preempt``, ``readmit``, ``commit``, ``first_token``,
    ``complete``) plus, at export time, every stage span whose `rids`
    include the request — the per-request waterfall.

Span identity is deterministic: `seq` is a global monotone counter in
host execution order (single-threaded serving loop), and the exported id
is derived from (track, cohort, rid, name, seq); all times come from the
simulated stage clocks. Two same-seed runs therefore produce
byte-identical exports (tested), which is the validation contract the
future async wall-clock loop must satisfy against this executor.

Memory is bounded by `max_spans` (a ring: oldest spans drop, the drop
count is surfaced in the metrics export); with the cap unhit the trace
is complete and determinism tests are unaffected.

Host regions (`Tracer.region`) are the other record, kept out of the
span deque: wall-clock spans of the host work inside a serving step,
named from `HOST_SPANS`. Each one is a `jax.profiler.TraceAnnotation`,
so while a profiler runs it lands on the calling thread's line of the
device trace, on the device trace's clock; and each one adds its
inclusive ms, its self ms (inclusive minus child regions on the same
thread) and one call to the registry's ``host.ms{span=}``,
``host.self_ms{span=}`` and ``host.calls{span=}`` counters. Regions are
live only on a tracer given a registry (the wall-clock backend's): the
simulated clocks' export stays free of wall time.
"""
from __future__ import annotations

import contextlib
import gc
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# span categories
STAGE = "stage"          # serial-resource occupancy (verify / draft nodes)
CLUSTER = "cluster"      # cluster-level activity (fuse, transit)
LIFECYCLE = "lifecycle"  # per-request state transitions (instants)

# host regions of the served path (wall clock), declared once:
# the engine thread's spans; all but engine.lull are top level and
# together tile a step
ENGINE_SPANS = (
    "engine.plan",            # pending scan, observation, admission, plan
    "engine.lull",            # in engine.plan: sleep to the next arrival
    "engine.prefill",         # cold requests: target enqueue, drafters
    "engine.draft",           # one cohort's drafting (draft-ahead, redraft)
    "engine.verify_dispatch",  # pad_trees and the verify enqueue
    "engine.verify_wait",     # blocked on the server's verify future
    "engine.logits_readback",  # slice and host copy of verify logits
    "engine.resolve",         # blocked on queued prefill / commit futures
    "engine.walk",            # argmax, acceptance walk, router update
    "engine.commit",          # commit enqueue, drafter commit
    "engine.finalize",        # accounting, _finalize, gamma feedback
    "engine.reconcile",       # draft-ahead survival (a redraft nests)
)
# children of engine.draft; the per-node ones carry `node`
DRAFT_SPANS = ("draft.snapshot", "draft.extend", "draft.decode",
               "draft.sample", "draft.fuse", "draft.tree")
# the drafter work a node does on the device's behalf
NODE_WORK_SPANS = ("draft.snapshot", "draft.extend", "draft.decode")
GC_SPAN = "host.gc"           # Python collector pauses, engine thread
# the verification-server thread, one per task kind
SERVER_SPANS = ("server.verify", "server.prefill", "server.commit",
                "server.drop")
HOST_SPANS = ENGINE_SPANS + DRAFT_SPANS + (GC_SPAN,) + SERVER_SPANS


@dataclass(frozen=True)
class Span:
    seq: int
    name: str
    cat: str                     # STAGE | CLUSTER | LIFECYCLE
    track: str                   # "verify" | "draft{i}" | "cluster" | "req{rid}"
    t0_ms: float
    t1_ms: float                 # == t0_ms for instants
    rid: int = -1                # owning request (lifecycle spans)
    cohort: int = -1             # cohort sequence number (-1 = none)
    rids: Tuple[int, ...] = ()   # requests a stage span covers
    args: Tuple[Tuple[str, object], ...] = ()

    @property
    def dur_ms(self) -> float:
        return self.t1_ms - self.t0_ms

    @property
    def is_instant(self) -> bool:
        return self.t1_ms == self.t0_ms

    def span_id(self) -> str:
        """Deterministic id: rid + cohort seq + name + global order."""
        return f"{self.track}/c{self.cohort}/r{self.rid}/{self.name}/{self.seq}"

    def get(self, key: str, default=None):
        for k, v in self.args:
            if k == key:
                return v
        return default


class _Region:
    """One live host region (see `Tracer.region`)."""
    __slots__ = ("tracer", "name", "node", "ann", "t0", "child")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer, self.name = tracer, name
        self.node = args.get("node")
        self.ann = TraceAnnotation(name, **args)

    def __enter__(self):
        self.ann.__enter__()
        self.tracer._stack().append(self)
        self.child = 0.0
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        ms = (time.perf_counter() - self.t0) * 1e3
        stack = self.tracer._stack()
        stack.pop()
        if stack:
            stack[-1].child += ms
        self.tracer._count(self.name, ms, ms - self.child, self.node)
        self.ann.__exit__(*exc)
        return False


_OFF = contextlib.nullcontext()


class Tracer:
    def __init__(self, enabled: bool = True, max_spans: int = 0,
                 metrics=None):
        self.enabled = enabled
        self.max_spans = int(max_spans)
        self.spans: Deque[Span] = deque(
            maxlen=self.max_spans if self.max_spans > 0 else None)
        self._seq = 0
        self.n_dropped = 0
        # host regions count into this registry; None keeps them inert
        self.metrics = metrics
        self.regions_live = bool(enabled) and metrics is not None
        self._local = threading.local()
        # (span, node) -> its (ms, self_ms, calls[, node_ms]) counters.
        # Each thread writes only its own spans' counters (the registry
        # has no lock)
        self._counters: Dict[tuple, tuple] = {}

    def span(self, name: str, cat: str, track: str, t0_ms: float,
             t1_ms: float, rid: int = -1, cohort: int = -1,
             rids: Tuple[int, ...] = (), **args) -> Optional[Span]:
        if not self.enabled:
            return None
        if self.max_spans > 0 and len(self.spans) == self.max_spans:
            self.n_dropped += 1
        s = Span(self._seq, name, cat, track, float(t0_ms), float(t1_ms),
                 int(rid), int(cohort), tuple(int(r) for r in rids),
                 tuple(sorted(args.items())))
        self._seq += 1
        self.spans.append(s)
        return s

    def instant(self, name: str, cat: str, track: str, t_ms: float,
                rid: int = -1, cohort: int = -1,
                rids: Tuple[int, ...] = (), **args) -> Optional[Span]:
        return self.span(name, cat, track, t_ms, t_ms, rid=rid,
                         cohort=cohort, rids=rids, **args)

    def mark(self, name: str, rid: int, t_ms: float, cohort: int = -1,
             **args) -> Optional[Span]:
        """Lifecycle instant on the request's own track."""
        return self.instant(name, LIFECYCLE, f"req{rid}", t_ms, rid=rid,
                            cohort=cohort, **args)

    # -------------------------------------------------------- host regions
    def region(self, name: str, **args):
        """Context manager timing host work on the wall clock (see the
        module docstring); `args` (such as ``cohort``, ``node``) annotate
        the profiler event, and a ``node`` also counts into
        ``host.node_ms{node=,span=}``. Does nothing unless regions are
        live."""
        if not self.regions_live:
            return _OFF
        return _Region(self, name, args)

    @contextlib.contextmanager
    def gc_regions(self):
        """While the block runs, every collection the calling thread
        makes is a `GC_SPAN` region (nested in whatever region it
        interrupts). Collections on other threads are not counted."""
        if not self.regions_live:
            yield
            return
        owner, open_ = threading.get_ident(), []

        def on_gc(phase, _info):
            if threading.get_ident() != owner:
                return
            if phase == "start":
                open_.append(_Region(self, GC_SPAN, {}).__enter__())
            elif open_:
                open_.pop().__exit__(None, None, None)

        gc.callbacks.append(on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(on_gc)
            while open_:
                open_.pop().__exit__(None, None, None)

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _count(self, name: str, ms: float, self_ms: float, node) -> None:
        c = self._counters.get((name, node))
        if c is None:
            m = self.metrics
            c = (m.counter("host.ms", span=name),
                 m.counter("host.self_ms", span=name),
                 m.counter("host.calls", span=name))
            if node is not None:
                c += (m.counter("host.node_ms", node=node, span=name),)
            self._counters[(name, node)] = c
        c[0].inc(ms)
        c[1].inc(self_ms)
        c[2].inc()
        if node is not None:
            c[3].inc(ms)

    def host_ms(self) -> Dict[str, float]:
        """{span: inclusive ms} of every host region counted so far."""
        # list() copies in one step, safe against the other thread adding
        return {name: c[0].value
                for (name, _), c in list(self._counters.items())}

    def node_ms(self, node: int) -> float:
        """Inclusive ms of drafter node `node`'s work (`NODE_WORK_SPANS`)."""
        if not self.regions_live:
            return 0.0
        return sum(self.metrics.value("host.node_ms", node=node, span=s)
                   for s in NODE_WORK_SPANS)

    # --------------------------------------------------------------- views
    def by_track(self, track: str) -> List[Span]:
        return [s for s in self.spans if s.track == track]

    def stage_tracks(self) -> List[str]:
        return sorted({s.track for s in self.spans if s.cat == STAGE})

    def stage_totals(self, track: str) -> Tuple[float, float]:
        """(busy_ms, idle_ms) of one serial stage track, from the trace
        alone: work spans are busy, `bubble` spans are measured idle."""
        busy = idle = 0.0
        for s in self.by_track(track):
            if s.cat != STAGE or s.is_instant:
                continue
            if s.name == "bubble":
                idle += s.dur_ms
            else:
                busy += s.dur_ms
        return busy, idle
