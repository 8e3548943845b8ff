"""Median device time of one fused-chunk program of the Nemotron-H torso
configuration (K gradient steps), from the device trace."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.chunk_ms(ctx)
