"""Median device time of one fused-chunk program of a sparse-attention torso
configuration (K gradient steps), from the device trace."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.chunk_ms(ctx)
