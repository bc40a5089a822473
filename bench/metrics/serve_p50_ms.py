"""Open loop: median over every request due in the window of the time from
its due time to its logits in host memory, in milliseconds (host clock).
A request never answered counts as infinitely late."""
import numpy as np


def read(ctx):
    if ctx.win.kind != "open":
        return None
    return 1e3 * float(np.percentile(ctx.win.latencies_s(), 50))
