"""Median ``inflight_ms`` of the ``fused.commit_staged`` spans that began in
the window: from a block's ``stage_block`` to the dispatch of its commit."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "inflight", 1.0)
