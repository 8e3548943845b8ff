"""Percent of the chip's bfloat16 peak that the model FLOPs one gradient step
of the Nemotron-H torso needs (``benchmark/shapes_ssm.step_flops``: every
block's products, the recurrence as the model writes it, the routed rows the
counter saw, five forward-equivalents, nothing recomputed) reach over the
chunk's device time a step. No clamp."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.step_mfu(ctx)
