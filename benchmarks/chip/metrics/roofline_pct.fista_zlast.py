"""Roofline share of the fused FISTA step of the z_L solve, in percent:
the least time the chip could take for the calls the trace holds, over the
summed device time of those calls. A call's least time is the larger of its
bytes (operands and result as the compiled program shapes them) over HBM
bandwidth and its operations over the bf16 peak; the step does about
FLOPS_PER_ELEMENT elementwise operations per element of its result."""

KERNEL = "_fista_step_kernel"
FLOPS_PER_ELEMENT = 24
BYTES_PER_ELEMENT_MOVED = 16       # three f32 reads and one f32 write


def read(ctx):
    calls = {n: b for n, (k, b) in ctx.kernels.items() if k == KERNEL}
    least = spent = 0.0
    for ops in ctx.trace.devices.values():
        for o in ops:
            if o.name in calls:
                nbytes = calls[o.name]
                elements = nbytes / BYTES_PER_ELEMENT_MOVED
                least += max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                             FLOPS_PER_ELEMENT * elements
                             / ctx.peaks["bf16_flops_per_s"])
                spent += (o.end - o.start) * 1e-9
    return 100.0 * least / spent if spent > 0 else None
