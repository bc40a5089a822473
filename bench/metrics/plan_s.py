"""Planner: host seconds of identify_parameters + map_network in set-up."""


def read(ctx):
    return ctx.setup["plan_s"]
