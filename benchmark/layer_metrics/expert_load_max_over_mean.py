"""The busiest held expert's assignments over the held experts' mean, from
the traced window's last chunk metrics (``route_counts [K, layers,
experts]``): 1.0 is even routing."""

from benchmark import shapes_torso


def read(ctx):
    if ctx.get("trace") is None or ctx.get("route_counts") is None:
        return None
    return shapes_torso.load_max_over_mean(ctx["torso"], ctx["route_counts"])
