"""Compiled-plan glue: percent of device op time spent in operations that
are not the overlay's Pallas kernels (XLA fusions, copies, transposes,
pools, concat, the classifier). From the profiler trace."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.op_s:
        return None
    return 100.0 * (t.op_s - t.kernel_s) / t.op_s
