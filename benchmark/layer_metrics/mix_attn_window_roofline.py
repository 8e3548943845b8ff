"""Percent of the roofline the sliding-window attention layers reach: five
projections and the products over the pairs a 2,048 window keeps
(``benchmark/shapes_mix.attention_counts``) over the time under
``torso.attn_window``. No clamp."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.attention_roofline(ctx, "sliding_attention")
