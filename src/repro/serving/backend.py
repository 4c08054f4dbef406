"""Execution backends: the mechanism half of the engine/backend split
(DESIGN.md §2.7).

`SpeculativeEngine` is *policy* — routing (Eq. 1-3), fusion (Eq. 4),
scheduling (Eq. 5-8/Alg. 2), admission — and an `ExecutionBackend` is
*mechanism*: every model execution (prefill, draft-decode on slot
snapshots, tree verification, cache commit/extend), every cache
admit/evict, and the serving clock. The engine never touches a
`ModelRunner` directly; it speaks this interface, so the same policy
stack runs unchanged against either implementation:

  * `SimulatedBackend` — the seed behaviour: model calls execute
    synchronously on the host in engine order, and time is the
    discrete-event simulated clock (`engine.clock_ms`, advanced by the
    StageClock/EventLog machinery). Every method is a 1:1 pass-through
    to the runners in the exact call order the pre-split engine used,
    so same-seed output (committed tokens, ServeStats, trace export) is
    byte-identical to the monolith (tested in tests/test_backend.py).

  * `AsyncJaxBackend` — a real wall-clock serving loop: the
    verification server is a dedicated worker thread that owns *all*
    target-model device state (verify forwards, prefill writes, commit
    extends, slot drops execute there in FIFO order — no cross-thread
    cache races, and JAX donation stays safe because target dispatches
    are totally ordered), while drafter models run on the engine
    thread. `verify_dispatch` returns immediately with a lazy handle —
    the forward is in flight on the worker (the GIL is released inside
    XLA) while the engine drafts the next cohort — and `device_get` is
    deferred to `VerifyHandle.result()`, so the acceptance walk pays
    the host transfer only when it actually consumes the logits.
    Driven by `serving/async_loop.WallClockExecutor`.

The losslessness contract is backend-independent: both backends execute
identical token-level math, so greedy tree acceptance + correction
always commits exactly the target's greedy continuation.
"""
from __future__ import annotations

import time
from abc import ABC, abstractmethod
from concurrent.futures import Future, ThreadPoolExecutor
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer
from repro.serving.runner import ModelRunner

# the tracer of a backend no engine has bound: its regions are inert
_NO_TRACER = Tracer(enabled=False)


class VerifyHandle:
    """Lazy verification result. `result()` materializes the (B, Gmax, V)
    logits on the caller; `times()` reports the measured wall span of the
    forward (None under the simulated backend, where the span lives on
    the simulated verify StageClock instead). The wait for the forward
    and the host copy are the caller's `engine.verify_wait` and
    `engine.logits_readback` regions."""

    def __init__(self, value: Optional[np.ndarray] = None,
                 future: Optional[Future] = None,
                 convert: Optional[Callable] = None,
                 span: Optional[dict] = None,
                 tracer: Tracer = _NO_TRACER):
        self._value = value
        self._future = future
        self._convert = convert
        self._span = span
        self._tracer = tracer

    def result(self) -> np.ndarray:
        """Materialize (blocking) and cache the verification logits."""
        if self._value is None:
            with self._tracer.region("engine.verify_wait"):
                raw = self._future.result()
            with self._tracer.region("engine.logits_readback"):
                self._value = self._convert(raw) if self._convert else raw
        return self._value

    def times(self) -> Optional[Tuple[float, float]]:
        """Measured wall (t0, t1) of the forward, or None if simulated."""
        if self._span is None:
            return None
        return self._span["t0"], self._span["t1"]


class ExecutionBackend(ABC):
    """Mechanism interface the engine serves against (DESIGN.md §2.7).

    Implementations own the target and drafter `ModelRunner`s (exposed
    as `.target` / `.drafters` for calibration and tests) plus the
    serving clock. Request-addressed: every method takes rids; slot
    bookkeeping is internal to the runners."""

    target: ModelRunner
    drafters: List[ModelRunner]
    #: True when `now_ms()` is wall time and model calls may be in
    #: flight concurrently (selects the WallClockExecutor)
    is_wallclock = False
    #: the bound engine's tracer (host regions of server tasks)
    tracer: Tracer = _NO_TRACER

    def __init__(self, target, drafter_specs, max_len: int,
                 paged: bool = False, page_size: int = 64,
                 pool_pages: int = 0):
        tcfg, tparams = target
        kw = dict(paged=paged, page_size=page_size, pool_pages=pool_pages)
        self.target = ModelRunner(tcfg, tparams, max_len, **kw)
        self.drafters = [ModelRunner(c, p, max_len, **kw)
                         for c, p, _ in drafter_specs]
        self._engine = None
        #: the bound engine's registry (the draft path's readback bytes)
        self.metrics = MetricsRegistry()

    def bind(self, engine):
        """Attach the engine (clock source for the simulated backend,
        tracer for the host regions, registry for the counters)."""
        self._engine = engine
        self.tracer = engine.tracer
        self.metrics = engine.metrics

    # ------------------------------------------------------------ clock
    @abstractmethod
    def now_ms(self) -> float:
        """Current serving time (simulated or wall, ms)."""

    # ------------------------------------------------- target lifecycle
    @abstractmethod
    def prefill_target(self, reqs: Dict[int, Sequence[int]],
                       batched: bool = False
                       ) -> Dict[int, Tuple[Optional[np.ndarray], float]]:
        """Admit + prefill each request's context on the target; returns
        {rid: (last-position logits, mean next-token logprob)}. With
        `batched`, cold requests share one masked `slot_extend` write
        (burst admission)."""

    @abstractmethod
    def verify_dispatch(self, rids: Sequence[int], tokens: np.ndarray,
                        rel_pos: np.ndarray, seg_mask: np.ndarray,
                        cohort: int = -1) -> VerifyHandle:
        """Start a tree verification forward; returns a lazy handle.
        `cohort` only annotates the host region of the forward."""

    @abstractmethod
    def commit_target(self, committed: Dict[int, List[int]]
                      ) -> Dict[int, np.ndarray]:
        """Extend the target's slot caches with the accepted tokens;
        returns each request's post-commit tail logits."""

    def commit_target_async(self, committed: Dict[int, List[int]]) -> Future:
        """Non-blocking commit variant for wall-clock executors; the
        future resolves to the tail logits. Default: synchronous."""
        fut: Future = Future()
        fut.set_result(self.commit_target(committed))
        return fut

    # ------------------------------------------------------ drafter ops
    # Drafters run on the engine thread under both backends. What the
    # draft path copies from drafter `di` to the host is counted as
    # `draft.readback_bytes{node=di}`.
    @abstractmethod
    def prefill_drafters(self, reqs: Dict[int, Sequence[int]],
                         batched: bool = False) -> Dict[int, List[float]]:
        """One-behind drafter prefill (context WITHOUT its last token);
        returns {rid: per-drafter mean logprobs} (the routing prior)."""

    @contextmanager
    def _readback(self, di: int):
        d = self.drafters[di]
        before = d.readback_bytes
        yield
        self.metrics.inc("draft.readback_bytes", d.readback_bytes - before,
                         node=di)

    def draft_snapshot(self, di: int, rids: Sequence[int]):
        """Speculative slot snapshot for drafter `di` (discard = rollback)."""
        return self.drafters[di].speculative_caches(rids)

    def draft_extend(self, di: int, snap, tokens: np.ndarray):
        """Teacher-force `tokens` (B, T) into a snapshot (optimistic
        draft-ahead warm-up); returns the advanced snapshot. Its logits
        are not read."""
        with self._readback(di):
            return self.drafters[di].extend_snapshot(snap, tokens)[1]

    def draft_decode(self, di: int, rids: Sequence[int],
                     tokens: np.ndarray, snap):
        """One drafting step on a snapshot; returns (logits, snapshot),
        the logits (rows, V) left on the device (rows past len(rids) are
        the bucket's padding)."""
        return self.drafters[di].decode_device(tokens, snap)

    def draft_decode_greedy(self, di: int, rids: Sequence[int],
                            tokens: np.ndarray, snap):
        """One greedy drafting step: the greedy pick of `draft_decode`'s
        logits, fetched in one readback. Returns (tokens (B,) int32, their
        probabilities (B,) float32, snapshot)."""
        with self._readback(di):
            lg, snap = self.draft_decode(di, rids, tokens, snap)
            tok, conf = self.drafters[di].pick(lg)
        B = len(rids)
        return tok[:B], conf[:B], snap

    def commit_drafters(self, committed: Dict[int, List[int]]) -> None:
        """Extend every drafter's slot caches (one-behind commit); their
        logits are not read."""
        for di, d in enumerate(self.drafters):
            with self._readback(di):
                d.extend_committed_device(committed)

    # -------------------------------------------------------- eviction
    @abstractmethod
    def drop_request(self, rid: int) -> None:
        """Release the request's slots on the target and every drafter
        (completion, shed, or preemption). No-op for unknown rids."""

    def sync(self) -> None:
        """Wait for queued backend work and raise the first failure of
        any of it, including work whose result nobody reads. No-op for
        synchronous backends, whose failures raise at the call."""

    def shutdown(self) -> None:
        """Release backend resources (worker threads)."""


class SimulatedBackend(ExecutionBackend):
    """Seed semantics: synchronous host execution in engine call order,
    simulated time. Pure mechanical indirection over the runners — the
    byte-identity contract (DESIGN.md §2.7) holds because each method is
    exactly the call the pre-split engine made, in the same order."""

    def now_ms(self) -> float:
        """Simulated engine clock (ms)."""
        return self._engine.clock_ms if self._engine is not None else 0.0

    def prefill_target(self, reqs, batched=False):
        """Prefill the target for {rid: ctx}, optionally as one burst."""
        if batched and len(reqs) > 1:
            return self.target.prefill_requests(reqs)
        return {rid: self.target.prefill_request(rid, ctx)
                for rid, ctx in reqs.items()}

    def prefill_drafters(self, reqs, batched=False):
        """Prefill every drafter; returns {rid: [mean logprob per drafter]}."""
        out: Dict[int, List[float]] = {rid: [] for rid in reqs}
        if batched and len(reqs) > 1:
            for d in self.drafters:
                res = d.prefill_requests(reqs)
                for rid in reqs:
                    out[rid].append(res[rid][1])
            return out
        for rid, ctx in reqs.items():
            for d in self.drafters:
                _, ll = d.prefill_request(rid, ctx)
                out[rid].append(ll)
        return out

    def verify_dispatch(self, rids, tokens, rel_pos, seg_mask, cohort=-1):
        """Run tree verification synchronously; handle is pre-resolved."""
        return VerifyHandle(
            value=self.target.verify(rids, tokens, rel_pos, seg_mask))

    def commit_target(self, committed):
        """Commit accepted tokens into the target cache; returns tails."""
        return self.target.extend_committed(committed)

    def drop_request(self, rid):
        """Evict `rid` from the target and every drafter cache."""
        self.target.drop(rid)
        for d in self.drafters:
            d.drop(rid)


class AsyncJaxBackend(ExecutionBackend):
    """Wall-clock backend: a single-worker verification server thread
    owns every target-model operation (totally ordered, so slot-cache
    donation and slot bookkeeping are race-free), drafters run on the
    engine thread, and verify forwards are genuinely in flight while
    the engine drafts ahead.

    `timeline` records each target task's measured wall span
    ({kind, t0, t1}, appended by the worker) — the executor drains it to
    attribute busy/idle time and emit wall-clock spans through the same
    §2.6 trace schema the simulated clocks use."""

    is_wallclock = True

    def __init__(self, target, drafter_specs, max_len: int,
                 paged: bool = False, page_size: int = 64,
                 pool_pages: int = 0):
        super().__init__(target, drafter_specs, max_len,
                         paged=paged, page_size=page_size,
                         pool_pages=pool_pages)
        self._t0 = time.monotonic()
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="verify-server")
        self.timeline: List[dict] = []
        self._timeline_pos = 0
        # exceptions raised by server tasks, in order; some futures are
        # never read (drops, prefills of shed requests), so the worker
        # records every failure and `sync`/`shutdown` re-raise it
        self._failures: List[BaseException] = []

    def now_ms(self) -> float:
        """Wall-clock ms since backend construction."""
        return (time.monotonic() - self._t0) * 1e3

    # ---------------------------------------------------- target worker
    def submit_target(self, kind: str, fn: Callable,
                      **args) -> Tuple[Future, dict]:
        """Queue `fn` on the verification server thread; returns (future,
        span) where span's t0/t1 are filled in by the worker. The task is
        the server thread's ``server.<kind>`` host region, annotated with
        `args`."""
        span = {"kind": kind, "t0": 0.0, "t1": 0.0}
        name = f"server.{kind}"

        def _task():
            span["t0"] = self.now_ms()
            try:
                with self.tracer.region(name, **args):
                    return fn()
            except BaseException as exc:
                self._failures.append(exc)
                raise
            finally:
                span["t1"] = self.now_ms()
                self.timeline.append(span)

        return self._pool.submit(_task), span

    def drain_timeline(self) -> List[dict]:
        """Completed target-task spans since the last drain (the list is
        append-only from the single worker, so reading a prefix is safe)."""
        end = len(self.timeline)
        out = self.timeline[self._timeline_pos:end]
        self._timeline_pos = end
        return out

    # ----------------------------------------------------- target ops
    def prefill_target(self, reqs, batched=True):
        """Blocking burst prefill (see `prefill_target_async`)."""
        return self.prefill_target_async(reqs).result()

    def prefill_target_async(self, reqs) -> Future:
        """Non-blocking burst prefill: queued on the verification server
        (FIFO — it lands before any later-dispatched verify that needs
        it). The future resolves to {rid: (logits, mean logprob)}."""
        reqs = dict(reqs)
        fut, _ = self.submit_target(
            "prefill", lambda: self.target.prefill_requests(reqs))
        return fut

    def verify_dispatch(self, rids, tokens, rel_pos, seg_mask, cohort=-1):
        """Queue tree verification on the server thread; lazy handle."""
        B = len(rids)
        vocab = self.target.cfg.vocab

        def _fwd():
            lg = self.target.verify_device(rids, tokens, rel_pos, seg_mask)
            lg.block_until_ready()   # compute timed here; transfer deferred
            return lg

        fut, span = self.submit_target("verify", _fwd, cohort=cohort)
        return VerifyHandle(
            future=fut, span=span,
            convert=lambda lg: np.asarray(lg[:B, :, :vocab]),
            tracer=self.tracer)

    def commit_target(self, committed):
        """Blocking cache commit (see `commit_target_async`)."""
        return self.commit_target_async(committed).result()

    def commit_target_async(self, committed) -> Future:
        """Non-blocking cache commit: the slot-extend forward (a
        verify-sized target dispatch) is queued on the verification
        server and overlaps the drafter commit + next draft on the
        engine thread. FIFO order guarantees it executes before the
        next verification reads the extended slots; the future resolves
        to the post-commit tail logits, which the engine only consumes
        at the *next* acceptance walk (`_resolve_tails`)."""
        committed = dict(committed)
        fut, _ = self.submit_target(
            "commit", lambda: self.target.extend_committed(committed))
        return fut

    def drop_request(self, rid):
        """Evict `rid`; the target-side release is queued FIFO."""
        # target slot release must serialize behind any queued prefill
        # that may still admit this rid (shed-after-queued-prefill)
        self.submit_target("drop", lambda: self.target.drop(rid))
        for d in self.drafters:
            d.drop(rid)

    # ---------------------------------------------------- drafter ops
    def prefill_drafters(self, reqs, batched=True):
        """Prefill every drafter on the engine thread (drafters are
        engine-thread-owned; only target ops route to the server)."""
        out: Dict[int, List[float]] = {rid: [] for rid in reqs}
        for d in self.drafters:
            res = d.prefill_requests(reqs) if (batched and len(reqs) > 1) \
                else {rid: d.prefill_request(rid, ctx)
                      for rid, ctx in reqs.items()}
            for rid in reqs:
                out[rid].append(res[rid][1])
        return out

    def _raise_failure(self):
        if self._failures:
            exc, self._failures = self._failures[0], []
            raise RuntimeError("a verification-server task failed") from exc

    def sync(self):
        """Wait until the server has run every queued task (FIFO, so one
        barrier task suffices), then raise the first task failure."""
        self._pool.submit(lambda: None).result()
        self._raise_failure()

    def shutdown(self):
        """Drain and join the verification server thread; raise the
        first task failure not yet raised by `sync`."""
        self._pool.shutdown(wait=True)
        self._raise_failure()


def make_backend(spec, target, drafter_specs, max_len: int,
                 paged: bool = False, page_size: int = 64,
                 pool_pages: int = 0) -> ExecutionBackend:
    """Resolve a backend spec: None/"sim" -> SimulatedBackend, "async" ->
    AsyncJaxBackend, or a ready ExecutionBackend instance. `paged` (from
    `CoSineConfig.paged_pool`) selects the paged KV pool in every runner
    the backend constructs (DESIGN.md §2.8)."""
    if isinstance(spec, ExecutionBackend):
        return spec
    kw = dict(paged=paged, page_size=page_size, pool_pages=pool_pages)
    if spec in (None, "sim"):
        return SimulatedBackend(target, drafter_specs, max_len, **kw)
    if spec == "async":
        return AsyncJaxBackend(target, drafter_specs, max_len, **kw)
    raise ValueError(f"unknown backend {spec!r} (expected 'sim' or 'async')")
