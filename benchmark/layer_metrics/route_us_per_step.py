"""Device time a gradient step spends under ``torso.route`` (norm, router,
top-k, sort, dispatch gather, combine; all passes), the median over chunk
executions over K."""

from benchmark import torso_trace


def read(ctx):
    return torso_trace.scope_us(ctx, "torso.route")
