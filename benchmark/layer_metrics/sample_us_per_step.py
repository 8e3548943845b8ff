"""Device time a gradient step spends under the chunk program's
``replay.sample`` scope (PER tree descent, beta schedule, IS weights), inside
the scan: the median over chunk executions of the scope's time over K."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "replay.sample", 1e6)
