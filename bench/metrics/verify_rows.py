"""Mean requests per verification (engine): `IterationRecord.batch`."""


def read(ctx):
    recs = ctx["records"]
    if not recs:
        return None
    return sum(r.batch for r in recs) / len(recs)
