"""``attn_roofline`` in the Qwen3-Next torso cell: the least time for the
projections (``q`` twice as wide for its gate) and the products over causal
pairs at 256-wide heads (``benchmark/shapes_linear.attention_counts``) over
the time under ``torso.attn_full``. No clamp."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.attention_roofline(ctx)
