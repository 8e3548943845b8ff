"""Device time a gradient step spends under ``torso.indexer`` (index
projections, norm, RoPE, index scores, the selection and the alignment
loss with its target; all passes), the median over chunk executions over
K."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.scope_us(ctx, "torso.indexer")
