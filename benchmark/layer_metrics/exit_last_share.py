"""Percent of the exit distribution on the last pass, from the traced window's
last chunk metrics (``exit_dist [K, R]``, the mean over rows of the
differentiated pass): 100 is a gate that never exits early, 0 one that never
reaches the last pass."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.exit_last_share(ctx)
