"""``attn_full_us_per_step`` in the Qwen3-Next torso cell: device time a
gradient step spends under ``torso.attn_full`` (norm, projections with the
gate, q/k norm, the partial rotation, the kernel at 256-wide heads, the
sigmoid gate, output projection; all passes)."""

from benchmark import linear_trace


def read(ctx):
    return linear_trace.scope_us(ctx, "torso.attn_full")
