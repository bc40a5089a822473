"""Engine / scheduler: mean RequestTrace.queue_s (due time to dispatch)
over every request of the window, in milliseconds."""
import numpy as np


def read(ctx):
    q = ctx.win.queue_s
    return 1e3 * float(np.mean(q)) if q else None
