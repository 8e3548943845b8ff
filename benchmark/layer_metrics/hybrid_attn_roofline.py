"""``attn_roofline`` in the LFM2 torso cell: the least time for the
projections and the products over causal pairs at 64-wide heads
(``benchmark/shapes_hybrid.attention_counts``; no credit for padding) over the
time under ``torso.attn_full``. No clamp."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.attention_roofline(ctx)
