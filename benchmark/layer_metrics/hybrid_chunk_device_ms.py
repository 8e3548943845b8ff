"""Median device time of one fused-chunk program of the LFM2 torso
configuration (K gradient steps), from the device trace."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.chunk_ms(ctx)
