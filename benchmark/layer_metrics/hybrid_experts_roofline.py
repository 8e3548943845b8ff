"""``experts_roofline`` in the LFM2 torso cell: the least time for the
assignments the chunk's ``route_counts`` gave the held experts
(``benchmark/shapes_hybrid.expert_counts``) over the time under
``torso.experts``. No clamp."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.experts_roofline(ctx)
