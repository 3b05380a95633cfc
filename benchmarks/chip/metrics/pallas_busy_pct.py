"""Share of device busy time spent in Pallas calls, in percent, over all
chips the cell uses."""
import trace_reduce as tr


def read(ctx):
    ops = [o for d in ctx.trace.devices.values() for o in d]
    busy = sum(tr.busy_ns(d) for d in ctx.trace.devices.values()) * 1e-9
    if not ops or busy <= 0 or not ctx.kernels:
        return None
    return 100.0 * tr.op_seconds(ops, lambda o: o.name in ctx.kernels) / busy
