"""``attn_full_us_per_step`` in the LFM2 torso cell: device time a gradient
step spends under ``torso.attn_full`` (norm, projections, q/k norm, RoPE, the
kernel at 64-wide heads, output projection; all passes)."""

from benchmark import hybrid_trace


def read(ctx):
    return hybrid_trace.scope_us(ctx, "torso.attn_full")
