"""Percent of the roofline the full attention layer reaches: five projections
and the products over the causal pairs
(``benchmark/shapes_mix.attention_counts``) over the time under
``torso.attn_full``. No clamp."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.attention_roofline(ctx, "full_attention")
