"""Whole-step model FLOP/s utilisation: the target's forward operations
for the tokens committed in the window (published vocabulary; no rejected
or draft tokens) over the window times the chip's bf16 peak, in percent."""

from bench import roofline


def read(ctx):
    if not ctx["commits"]:
        return None
    flops = sum(roofline.token_flops(ctx["target"], pos + i)
                for pos, n in ctx["commits"] for i in range(n))
    return 100.0 * flops / (ctx["window_s"]
                            * ctx["peak"]["bf16_flops_per_s"])
