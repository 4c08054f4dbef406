"""Verify step against its roofline (kernels; the verify step as XLA runs
it): the least time of the window's verifications (weights read once per
call, or the operations of the tree nodes verified, whichever bounds;
`bench/roofline.py`) over the device time of the verify program."""

from bench import roofline

PROGRAM = "slot_verify_chunk"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or PROGRAM not in tr["programs"] or not ctx["records"]:
        return None
    secs, calls = tr["programs"][PROGRAM]
    recs = ctx["records"]
    # the records' calls and the trace's are the same verifications, up to
    # the window's edges: compare per call
    ctx_len = ctx.get("mean_context", 0)
    least = sum(roofline.call_least_seconds(
        ctx["target"], [ctx_len] * r.big_gamma, ctx["peak"])[0]
        for r in recs) / len(recs)
    return 100.0 * least / (secs / calls)
