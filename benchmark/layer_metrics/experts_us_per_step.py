"""Device time a gradient step spends under ``torso.experts`` (the grouped
products of the held experts; all passes), the median over chunk executions
over K."""

from benchmark import torso_trace


def read(ctx):
    return torso_trace.scope_us(ctx, "torso.experts")
