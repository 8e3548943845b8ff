"""Median device time of one fused-chunk program of the Qwen3-Next torso
configuration (K gradient steps), from the device trace."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.chunk_ms(ctx)
