"""Percent of an ``E`` block's assignments that the routing bias changed (in
the top 6 of score + bias, not in the top 6 of the score), from the traced
window's last chunk metrics (``bias_swapped [K, E blocks]``), the mean over
blocks: 0 is a bias that does nothing."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.swapped_share(ctx)
