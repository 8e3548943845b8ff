"""``experts_roofline`` in the Qwen3-Next torso cell: the least time for the
assignments the chunk's ``route_counts`` gave the held experts
(``benchmark/shapes_torso.expert_counts``) over the time under
``torso.experts``. No clamp."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.experts_roofline(ctx)
