"""``experts_roofline`` in a sparse-attention torso cell: the least time for
the assignments the chunk's ``route_counts`` gave the held experts
(``benchmark/shapes_torso.expert_counts``) over the time under
``torso.experts``. No clamp."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.experts_roofline(ctx)
