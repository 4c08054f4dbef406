"""Programs compiled or loaded from the persistent cache inside the
measured window (runner): JAX's backend-compile events, which cover both;
set-up should leave none."""


def read(ctx):
    return float(ctx["window_compiles"])
