"""Percent of ``ln R`` that the entropy of the mean exit distribution reaches,
from the traced window's last chunk metrics (``exit_dist [K, R]``): 100 is
uniform over the passes, 0 a gate that always leaves at the same pass."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.exit_entropy_share(ctx)
