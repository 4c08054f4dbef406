"""Host time of the acceptance walk per verification (engine): the
`engine.logits_readback` (slice and host copy of the verify logits) and
`engine.walk` (argmax, tree acceptance, router update) host spans' ms over
the window's records; nothing where the records carry no host spans."""
from bench.spans import per_verification

SPANS = ("engine.logits_readback", "engine.walk")


def read(ctx):
    return per_verification(ctx, SPANS)
