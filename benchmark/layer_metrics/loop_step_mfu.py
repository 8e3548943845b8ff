"""Percent of the chip's bfloat16 peak that the model FLOPs one gradient step
of the looped Ouro torso needs (``benchmark/shapes_loop.step_flops``: the
attention and SwiGLU products of every layer application, five
forward-equivalents, nothing recomputed) reach over the chunk's device time
a step. No clamp."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.step_mfu(ctx)
