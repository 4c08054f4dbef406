"""Share of draft-ahead entries that survived their verification
(wall-clock loop): the executor's `n_survived` over `n_survived +
n_invalidated` in the window, in percent."""


def read(ctx):
    n = ctx["survived"] + ctx["invalidated"]
    if not n:
        return None
    return 100.0 * ctx["survived"] / n
