"""Device time a gradient step spends under ``torso.mlp`` in the looped Ouro
torso cell (both norms and the SwiGLU of every layer application; all
passes)."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.scope_us(ctx, "torso.mlp")
