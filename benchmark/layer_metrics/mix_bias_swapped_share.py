"""Percent of an expert layer's assignments that the routing bias changed (in
the top 8 of score + bias, not in the top 8 of the score), from the traced
window's last chunk metrics (``bias_swapped [K, expert layers]``), the mean
over layers: 0 is a bias that does nothing."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.swapped_share(ctx)
