"""Host time of the commit per verification (engine): the `engine.commit`
host span's ms (target commit enqueue, drafter commit) over the window's
records; nothing where the records carry no host spans."""
from bench.spans import per_verification

SPANS = ("engine.commit",)


def read(ctx):
    return per_verification(ctx, SPANS)
