"""Serving telemetry layer (DESIGN.md §2.6).

Three pieces, deterministic on the simulated clocks:

  * `obs.trace`   — `Tracer`: per-request lifecycle + per-stage occupancy
                    spans built from instrumentation hooks in the serving
                    stack (engine / pipeline / cluster / admission); and
                    host regions (`Tracer.region`, names in `HOST_SPANS`):
                    wall-clock spans of the served path's host work,
                    written into the JAX profiler's trace and counted
                    into the registry (``host.ms`` / ``host.self_ms`` /
                    ``host.calls``).
  * `obs.metrics` — `MetricsRegistry`: counters, gauges and fixed-bucket
                    histograms — the single source behind `ServeStats`'
                    aggregates — plus the controller `DecisionLog`
                    (every λ/γ/admission decision with its inputs).
  * `obs.export`  — Chrome/Perfetto ``trace_event`` JSON export and a
                    flat metrics JSON (byte-identical across same-seed
                    runs), consumed by ``python -m repro.obs.summarize``.

The span schema is the contract the future async wall-clock serve loop
must emit, so its measured overlap can be diffed against the
discrete-event executor's prediction (ROADMAP headline item).
"""
from repro.obs.metrics import DecisionLog, MetricsRegistry
from repro.obs.trace import HOST_SPANS, Span, Tracer

__all__ = ["DecisionLog", "HOST_SPANS", "MetricsRegistry", "Span", "Tracer"]
