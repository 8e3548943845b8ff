"""``experts_us_per_step`` in the LFM2 torso cell: device time a gradient
step spends under ``torso.experts`` (the grouped products and their selects;
all passes)."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.scope_us(ctx, "torso.experts")
