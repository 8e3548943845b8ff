"""Percent of the roofline the Mamba-2 mixers reach without their
recurrence: the two projections' FLOPs against ``z``, ``xBC`` and ``y``
written and read once in the compute dtype
(``benchmark/shapes_ssm.mamba_counts``) over the time under ``torso.mamba``.
No clamp."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.mamba_roofline(ctx)
