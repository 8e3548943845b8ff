"""Device time a gradient step spends in the ungated shared expert (one SwiGLU
of 1,024 every token goes through; the ``torso.shared_expert`` scope), forward
and backward, in microseconds."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.scope_us(ctx, "torso.shared_expert")
