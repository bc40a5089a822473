"""Images whose logits reached the host in the window, over the window
(host clock). An open window lasts until its last answer, and at least
the run's seconds."""


def read(ctx):
    win = ctx.win
    return win.completed_in_window / win.window_s if win.window_s else None
