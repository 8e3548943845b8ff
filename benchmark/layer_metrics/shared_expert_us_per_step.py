"""Device time a gradient step spends under ``torso.shared_expert`` in the
Qwen3-Next torso cell (the shared expert's SwiGLU and its scalar sigmoid gate,
every token, every layer; all passes)."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.scope_us(ctx, "torso.shared_expert")
