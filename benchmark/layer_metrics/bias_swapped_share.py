"""Percent of a layer's assignments that the routing bias changed (in the
top 4 of score + bias, not in the top 4 of the score), from the traced
window's last chunk metrics (``bias_swapped [K, layers with experts]``), the
mean over layers: 0 is a bias that does nothing."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.swapped_share(ctx)
