"""``route_us_per_step`` in the Qwen3-Next torso cell: device time a gradient
step spends under ``torso.route`` (norm, the softmax router over 512 experts
and its top-10, sort, dispatch gather, combine; all passes)."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.scope_us(ctx, "torso.route")
