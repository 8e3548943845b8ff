"""Percent of the roofline the held relu2 experts reach: two products for
every assignment the chunk's ``route_counts`` counted
(``benchmark/shapes_ssm.expert_counts``) over the time under
``torso.experts``. No clamp."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.experts_roofline(ctx)
