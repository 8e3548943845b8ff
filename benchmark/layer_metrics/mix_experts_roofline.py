"""Percent of the roofline the held experts' grouped products reach: three
matrices for every assignment the counter saw
(``benchmark/shapes_mix.expert_counts``) over the time under
``torso.experts``. No clamp."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.experts_roofline(ctx)
