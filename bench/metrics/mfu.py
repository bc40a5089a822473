"""Model step: direct FLOPs per image (convs and classifier) times the
images answered in the traced slice, over that slice times the chips
times the peak FLOP/s, in percent."""
from bench import counts


def read(ctx):
    t = ctx.trace
    if t is None or not t.window_s or ctx.peak is None:
        return None
    flops = counts.model_flops(ctx.graph) * ctx.traced_done
    return 100.0 * flops / (t.window_s * ctx.chips
                            * ctx.peak["flops_per_s"])
