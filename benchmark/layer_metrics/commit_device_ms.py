"""Median device time of one execution of the block-commit program
(``jit_commit``: ring write and tree insert of one staged block); stderr splits
it by ``ingest.ring_write`` / ``ingest.tree_insert``."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "commit", 1e3)
