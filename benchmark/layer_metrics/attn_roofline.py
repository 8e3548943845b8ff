"""The attention layers' share of their roofline: the least time the chip
could take for a step's projections and masked attention products
(``benchmark/shapes_torso.attention_counts``: kept pairs only, five
forward-equivalents, nothing recomputed) over the time under
``torso.attn_window`` and ``torso.attn_full``. No clamp."""

from benchmark import torso_trace


def read(ctx):
    return torso_trace.attn_roofline(ctx)
