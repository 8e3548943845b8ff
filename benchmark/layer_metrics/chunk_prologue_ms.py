"""Device time of the chunk program's operations outside the scan's ``while``
body, per execution (median): what XLA hoisted above the K steps, such as the
whole-ring cast or re-layout; stderr splits it by the scope each op came from."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "prologue", 1e3)
