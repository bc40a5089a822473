"""Kernels: the conv layers' least time on this chip (each layer the larger
of its direct-conv FLOPs over peak FLOP/s and its least bytes over HBM
bytes/s), for every image answered in the traced slice, over the device
time of the overlay's Pallas kernels, in percent."""
from bench import counts


def read(ctx):
    t = ctx.trace
    if t is None or not t.kernel_s or ctx.peak is None:
        return None
    least = counts.conv_least_time_s(ctx.graph, ctx.peak)
    return 100.0 * least * ctx.traced_done / t.kernel_s
