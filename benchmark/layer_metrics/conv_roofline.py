"""Percent of the roofline the short-convolution operators reach: the larger
of the two projections' FLOPs at peak and the gates' and taps' bytes read
and written once (``benchmark/shapes_hybrid.conv_counts``) over the time under
``torso.conv``. No clamp."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.conv_roofline(ctx)
