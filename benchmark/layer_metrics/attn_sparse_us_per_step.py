"""Device time a gradient step spends under ``torso.attn_sparse`` (norm,
main projections, q/k norm, RoPE, attention over the selection, output
projection; all passes), the median over chunk executions over K."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.scope_us(ctx, "torso.attn_sparse")
