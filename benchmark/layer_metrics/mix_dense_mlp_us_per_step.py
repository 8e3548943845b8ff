"""Device time a gradient step spends in the leading dense layer's SwiGLU of
6,144 with its two norms (the ``torso.mlp`` scope), forward and backward, in
microseconds."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.scope_us(ctx, "torso.mlp")
