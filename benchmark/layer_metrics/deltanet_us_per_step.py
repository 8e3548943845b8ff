"""Device time a gradient step spends under ``torso.deltanet`` in the
Qwen3-Next torso cell (the operator's norm, both input projections, the four
taps and SiLU, the output norm and gate, ``out_proj``; all passes)."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.scope_us(ctx, "torso.deltanet")
