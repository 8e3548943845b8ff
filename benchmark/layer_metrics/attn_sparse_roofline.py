"""The sparse attention's share of its roofline: the least time the chip
could take for a step's main projections and attention products over the
**selected** pairs only (``benchmark/shapes_sparse.attention_counts``) over
the time under ``torso.attn_sparse``. No clamp."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.attention_roofline(ctx)
