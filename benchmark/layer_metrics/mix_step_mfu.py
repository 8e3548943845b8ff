"""Percent of the chip's bfloat16 peak that the model FLOPs one gradient step
of the Trinity-Mini torso needs (``benchmark/shapes_mix.step_flops``: every
layer's products, the pairs each mask keeps, the routed rows the counter saw,
five forward-equivalents, nothing recomputed) reach over the chunk's device
time a step. No clamp."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.step_mfu(ctx)
