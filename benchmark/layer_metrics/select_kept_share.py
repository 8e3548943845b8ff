"""Percent of the causal query-key pairs the selection kept, from the traced
window's last chunk metrics (``select_counts [K, layers, tokens /
kv_chunk_size]``): 23.437 at 16,384 tokens and ``topk`` 2,048 whatever the
batch; anything else is a selection that is not ``min(t + 1, topk)`` a
query."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.kept_share(ctx)
