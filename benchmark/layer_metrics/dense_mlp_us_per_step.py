"""Device time a gradient step spends under ``torso.mlp`` (the dense
layer's norm and SwiGLU feed-forward; all passes)."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.scope_us(ctx, "torso.mlp")
