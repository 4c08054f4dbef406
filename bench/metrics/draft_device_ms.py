"""Device time of the drafters' speculation programs per cohort (model
step), from the profiler trace: snapshot gathers, teacher-forced extends
and decode steps on snapshots, over the window's verifications."""

PROGRAMS = ("gather_slots", "extend", "decode_step")


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["records"]:
        return None
    secs = sum(tr["programs"].get(p, (0.0, 0))[0] for p in PROGRAMS)
    if secs <= 0:
        return None
    return 1e3 * secs / len(ctx["records"])
