"""Device time per call of the target's verify program (model step), from
the profiler trace: `slot_verify_chunk`'s summed device time over calls."""

PROGRAM = "slot_verify_chunk"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or PROGRAM not in tr["programs"]:
        return None
    secs, calls = tr["programs"][PROGRAM]
    return 1e3 * secs / calls
