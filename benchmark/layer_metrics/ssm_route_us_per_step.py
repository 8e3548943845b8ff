"""Device time a gradient step spends routing in the ``E`` blocks (the norm,
the sigmoid router over 128 experts with its bias, the sort, the gathers to
and from the sorted buffer; the ``torso.route`` scope), forward and
backward, in microseconds."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.scope_us(ctx, "torso.route")
