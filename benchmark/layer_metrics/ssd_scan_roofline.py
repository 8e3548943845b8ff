"""Percent of the roofline the Mamba-2 recurrence reaches: its needed work
as the model writes it, token by token (decay, rank-one write and read of a
``[64, 128]`` state a head and token against ``xs, B, C, dt, y`` read or
written once in float32; ``benchmark/shapes_ssm.ssd_scan_counts``: the count
knows neither the chunk nor the form) over the time under
``torso.ssd_scan``. No clamp."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.ssd_scan_roofline(ctx)
