"""Tokens committed per request per verification (engine): the sum of
`IterationRecord.committed` over the sum of `batch` in the window."""


def read(ctx):
    rows = sum(r.batch for r in ctx["records"])
    if not rows:
        return None
    return sum(r.committed for r in ctx["records"]) / rows
