"""Device time a gradient step spends in the held relu2 experts' grouped
products (two matrices an expert; the ``torso.experts`` scope), forward and
backward, in microseconds."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.scope_us(ctx, "torso.experts")
