"""Share of the window the verification server waited for drafts
(wall-clock loop): the union of the records' idle spans (each record's
`verify_idle_ms` ends where its verification starts), clipped to the
window, in percent of the window."""
from bench.trace import merge


def read(ctx):
    if not ctx["records"]:
        return None
    lo, hi = ctx["window_ms"]
    spans = [(max(r.verify_start_ms - r.verify_idle_ms, lo),
              min(r.verify_start_ms, hi), "idle") for r in ctx["records"]]
    idle_ms = sum(b - a for a, b in merge([s for s in spans if s[1] > s[0]]))
    return 100.0 * idle_ms / (hi - lo)
