"""Percent of the roofline the Gated DeltaNet operators reach without their
recurrence: the larger of the three projections' FLOPs at peak and ``[q, k,
v, z]`` written and read once in the compute dtype
(``benchmark/shapes_linear.deltanet_counts``) over the time under
``torso.deltanet``. No clamp."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.deltanet_roofline(ctx)
