"""Device time a gradient step spends under the chunk program's
``replay.gather`` scope (the batch's rows out of the ring), inside the scan:
the median over chunk executions of the scope's time over K."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "replay.gather", 1e6)
