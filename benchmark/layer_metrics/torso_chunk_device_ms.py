"""Median device time of one fused-chunk program of a torso configuration
(K gradient steps), from the device trace."""

from benchmark import torso_trace


def read(ctx):
    found = torso_trace.analyse(ctx)
    return None if found is None else float(found["total"] * 1e3)
