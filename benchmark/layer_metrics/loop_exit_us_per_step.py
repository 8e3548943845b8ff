"""Device time a gradient step spends under ``torso.exit`` (every pass's
final norm, pool and gate, the exit distribution and its entropy; all
passes)."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.scope_us(ctx, "torso.exit")
