"""Closed loop: the window's length over the requests completed in it,
in milliseconds (host clock, logits in host memory)."""


def read(ctx):
    win = ctx.win
    if win.kind != "closed" or not win.completed_in_window:
        return None
    return 1e3 * win.window_s / win.completed_in_window
