"""The step's share of the chips' bf16 peak, in percent: the contraction
FLOPs of one iteration, as the configuration's model module counts them
(``contraction_flops``), times the iterations of the traced window, over
the window's length times chips x peak."""


def read(ctx):
    flops = ctx.model.contraction_flops(ctx.config) * ctx.iterations
    seconds = ctx.trace.window_s
    if not ctx.trace.devices or seconds <= 0:
        return None
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (seconds * peak)
