"""``route_us_per_step`` in a sparse-attention torso cell: device time a
gradient step spends under ``torso.route`` (norm, router, top-k, sort,
dispatch gather, combine; all passes)."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.scope_us(ctx, "torso.route")
