"""Time the engine thread blocked on the verification server per
verification (wall-clock loop): the `engine.verify_wait` (the verify
future) and `engine.resolve` (queued prefill and commit futures) host
spans' ms over the window's records; nothing where the records carry no host spans."""
from bench.spans import per_verification

SPANS = ("engine.verify_wait", "engine.resolve")


def read(ctx):
    return per_verification(ctx, SPANS)
