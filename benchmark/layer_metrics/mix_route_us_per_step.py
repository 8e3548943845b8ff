"""Device time a gradient step spends routing in the expert layers (the norm,
the sigmoid router over 128 experts with its bias, the sort, the gathers to
and from the sorted buffer, the post-norm on routed + shared; the
``torso.route`` scope), forward and backward, in microseconds."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.scope_us(ctx, "torso.route")
