"""Host time of drafting per verification (engine): the `engine.draft`
host span's inclusive ms over the window's records (every draft-ahead and
redraft, with its snapshot, extend, decode, sampling, fusion and tree
children); nothing where the records carry no host spans."""
from bench.spans import per_verification

SPANS = ("engine.draft",)


def read(ctx):
    return per_verification(ctx, SPANS)
