"""The fused chunk's share of its roofline: the least time the chip could
take for K steps (the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s, from ``benchmark/shapes.py`` and ``benchmark/peaks.json``) over
the chunk program's median device time. No clamp: above 100 % the counts
are wrong."""

import numpy as np

from benchmark import shapes, trace_reduce


def read(ctx):
    trace, peak = ctx.get("trace"), ctx.get("peak")
    if trace is None or peak is None:
        return None
    start, end = trace_reduce.program_runs(trace, ctx["chunk_program"])
    if start.size == 0:
        return None
    least, bound = shapes.roofline_seconds(ctx["counts"], peak)
    ctx["log"](f"[roofline] one step needs {ctx['counts']['flops']:.4g} FLOP "
               f"and {ctx['counts']['bytes']:.4g} B: bound by {bound}, "
               f"{least * 1e6:.3f} us a step at peak")
    return float(100.0 * least * ctx["k"] / np.median(end - start))
