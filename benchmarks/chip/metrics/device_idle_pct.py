"""Share of the traced window in which no op runs on a chip, in percent;
the largest over the chips the cell uses."""
import trace_reduce as tr


def read(ctx):
    window = ctx.trace.window[1] - ctx.trace.window[0]
    idle = [1.0 - tr.busy_ns(ops) / window
            for ops in ctx.trace.devices.values()]
    return 100.0 * max(idle) if idle and window > 0 else None
