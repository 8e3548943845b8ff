"""Device time a gradient step spends under ``torso.delta_scan`` in the
Qwen3-Next torso cell (the l2 norms, ``g``, ``beta`` and the gated delta
rule's recurrence, forward and backward; all passes)."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.scope_us(ctx, "torso.delta_scan")
