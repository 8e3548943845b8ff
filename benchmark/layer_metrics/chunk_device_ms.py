"""Median device time of one fused-chunk program (K gradient steps), from
the device trace."""

import numpy as np

from benchmark import trace_reduce


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    start, end = trace_reduce.program_runs(trace, ctx["chunk_program"])
    if start.size == 0:
        return None
    return float(np.median(end - start) * 1e3)
