"""``attn_roofline`` in the looped Ouro torso cell: the least time for the
projections and the products over causal pairs at 16 heads of 128, every
layer application of every pass (``benchmark/shapes_loop.attention_counts``;
nothing recomputed) over the time under ``torso.attn_full``. No clamp."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.attention_roofline(ctx)
