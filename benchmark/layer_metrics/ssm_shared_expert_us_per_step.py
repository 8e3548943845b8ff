"""Device time a gradient step spends in the ungated relu2 shared expert of
the ``E`` blocks (two matrices of 3,712, every token; the
``torso.shared_expert`` scope), forward and backward, in microseconds."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.scope_us(ctx, "torso.shared_expert")
