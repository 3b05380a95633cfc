"""Roofline share of the fused FISTA step of the z_L solve, in percent:
the least time the chip could take for the ``_fista_step_kernel`` calls
the trace holds, over the summed device time of those calls.

A call's least time is the HBM bytes moved for it over the HBM bandwidth. The
bytes are ``counts.pallas_calls``'s: the call's result and its five
operands (``z_prev``, ``z_cur`` and ``a``, [rows, N]; the label and mask
columns, [rows, 1]) as the compiled program lays each out on one device,
padded to the layout's tile (a [rows, 1] column stores 128 lanes a row),
each counted only where it lies in HBM. The compiler keeps some of them in
VMEM (layout ``S(1)``) from one call to the next; those move no HBM bytes,
and counting them would read above 100%.

An operand that an async copy (``copy-start``/``copy-done``) brought into
VMEM from HBM counts the bytes that copy read, once for the solve, at the
first call that reads it. An array that another instruction wrote into
VMEM is that instruction's traffic and counts nothing here, while the
calls' device time still counts: so the share moves with where the
compiler places the arrays, not only with the kernel. Where every call of
a cell found all its arrays written into VMEM by other instructions, the
reader would return None and the cell could not report the metric; the
stage path at pubmed's and coauthor_physics's sizes does not. The step's
two dozen elementwise operations an element run on the vector unit, whose
peak is not published; against the bf16 peak they would bound no call, so
no operation bound is kept."""

KERNEL = "_fista_step_kernel"


def read(ctx):
    calls = {n: b for n, (k, b) in ctx.kernels.items() if k == KERNEL}
    moved = spent = 0.0
    for ops in ctx.trace.devices.values():
        for o in ops:
            if o.name in calls:
                moved += calls[o.name]
                spent += (o.end - o.start) * 1e-9
    if moved <= 0 or spent <= 0:
        return None
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / spent
