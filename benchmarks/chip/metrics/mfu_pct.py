"""The step's share of the chips' bf16 peak, in percent: the update
family's contractions (``counts.contraction_flops``) over the traced window,
divided by chips x peak."""
from counts import contraction_flops


def read(ctx):
    cfg = ctx.config
    flops = contraction_flops(cfg["nodes"], cfg["hidden"], cfg["layers"]) \
        * ctx.iterations
    seconds = ctx.trace.window_s
    if not ctx.trace.devices or seconds <= 0:
        return None
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (seconds * peak)
