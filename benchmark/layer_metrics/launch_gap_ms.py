"""Median idle gap on the device between the end of one fused-chunk program
and the start of the next, from the device trace."""

import numpy as np

from benchmark import trace_reduce


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    start, end = trace_reduce.program_runs(trace, ctx["chunk_program"])
    if start.size < 2:
        return None
    return float(np.median(start[1:] - end[:-1]) * 1e3)
