"""Device time a gradient step spends under the chunk program's
``replay.writeback`` scope (``dper.update_from_td``: both trees repaired),
inside the scan: the median over chunk executions of the scope's time over K."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "replay.writeback", 1e6)
