"""``route_us_per_step`` in the LFM2 torso cell: device time a gradient step
spends under ``torso.route`` (norm, the sigmoid router and its two top-k, sort,
dispatch gather, combine; all passes)."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.scope_us(ctx, "torso.route")
