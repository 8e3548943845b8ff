"""Median device time of one fused-chunk program of the Trinity-Mini torso
configuration (K gradient steps), from the device trace."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.chunk_ms(ctx)
