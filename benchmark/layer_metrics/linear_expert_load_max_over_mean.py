"""``expert_load_max_over_mean`` in the Qwen3-Next torso cell: the busiest
held expert's assignments over the held experts' mean, from the traced
window's last chunk metrics (``route_counts``): 1.0 is even."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.load_max_over_mean(ctx)
