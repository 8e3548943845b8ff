"""Device time a gradient step spends under ``torso.conv`` (the short
convolution operators: norm, ``in_proj``, both gates, the taps, ``out_proj``;
all passes), from the device trace and the chunk program's compiled text."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.scope_us(ctx, "torso.conv")
