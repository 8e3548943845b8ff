"""Percent of the roofline the attention block reaches: projections and
products over the causal pairs at 32 heads of 128
(``benchmark/shapes_ssm.attention_counts``) over the time under
``torso.attn_full``. No clamp."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.attention_roofline(ctx)
