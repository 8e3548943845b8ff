"""Median device time of one fused-chunk program of the looped Ouro torso
configuration (K gradient steps), from the device trace."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.chunk_ms(ctx)
