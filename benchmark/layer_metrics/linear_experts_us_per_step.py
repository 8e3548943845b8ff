"""``experts_us_per_step`` in the Qwen3-Next torso cell: device time a
gradient step spends under ``torso.experts`` (the grouped products over 16
held experts of ~80 rows each and their selects; all passes)."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.scope_us(ctx, "torso.experts")
