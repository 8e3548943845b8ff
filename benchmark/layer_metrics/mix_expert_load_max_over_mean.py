"""The busiest held expert's assignments over the held experts' mean, an
expert layer and a step at a time, averaged, from the traced window's last
chunk metrics (``route_counts [K, expert layers, 128]``): 1 is an even
load."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.load_max_over_mean(ctx)
