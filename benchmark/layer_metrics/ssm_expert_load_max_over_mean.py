"""The busiest held expert's assignments over the held experts' mean, an
``E`` block and a step at a time, averaged, from the traced window's last
chunk metrics (``route_counts [K, E blocks, 128]``): 1 is an even load."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.load_max_over_mean(ctx)
