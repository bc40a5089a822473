"""Process start to the first timed request: imports, plan, weights,
compile or cache load, and warm-up (host clock)."""


def read(ctx):
    return ctx.setup["setup_s"]
