"""The expert products' share of their roofline: the least time the chip
could take for the assignments the chunk's ``route_counts`` gave the held
experts (``benchmark/shapes_torso.expert_counts``) over the time under
``torso.experts``. No clamp."""

from benchmark import torso_trace


def read(ctx):
    return torso_trace.experts_roofline(ctx)
