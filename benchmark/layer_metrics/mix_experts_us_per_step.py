"""Device time a gradient step spends in the held experts' grouped products
(the ``torso.experts`` scope), forward and backward, in microseconds."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.scope_us(ctx, "torso.experts")
