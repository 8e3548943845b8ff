"""Percent of the roofline the gated delta rule reaches: the recurrence's
needed work as the model writes it, token by token (three ``[128, 128]``
products a value head and token against ``q, k, v, o, g, beta`` read or
written once; ``benchmark/shapes_linear.delta_scan_counts``: the count knows
neither the chunk nor the form) over the time under ``torso.delta_scan``.
No clamp."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.delta_scan_roofline(ctx)
