"""Engine / scheduler: requests served over the bucket slots dispatched
in the window, in percent (the rest is padding)."""


def read(ctx):
    win = ctx.win
    slots = sum(b * n for b, n in win.dispatched.items())
    return 100.0 * win.served / slots if slots else None
