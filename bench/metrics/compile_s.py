"""Compiled plan build: host seconds of the engine's construction with
warmup=True (compile or cache load, plus two ticks per bucket)."""


def read(ctx):
    return ctx.setup["compile_s"]
