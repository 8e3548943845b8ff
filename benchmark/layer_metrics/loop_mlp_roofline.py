"""The least time for the SwiGLU's three products, every layer application of
every pass (``benchmark/shapes_loop.mlp_counts``; nothing recomputed) over the
time under ``torso.mlp``. No clamp."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.mlp_roofline(ctx)
