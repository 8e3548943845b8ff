"""Median ``wait_ms`` of the ``fused.stage_block`` spans that began in the
window: how long the oldest row of a block sat in the host staging ring before
its ``device_put`` (stderr: p95, max, count)."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "staging_wait", 1.0)
