"""Percent of its state a DeltaNet head keeps from one token to the next: the
mean of ``exp(g)`` over heads, tokens, sequences and layers, from the traced
window's last chunk metrics (``delta_kept [K, DeltaNet layers]``): 100 is a
plain delta rule that never forgets, 0 a layer with no memory."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.kept_share(ctx)
