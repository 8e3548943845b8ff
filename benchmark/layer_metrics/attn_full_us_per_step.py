"""Device time a gradient step spends under ``torso.attn_full`` (the full
causal layers: norm, projections, YaRN RoPE, kernel, output projection; all
passes), the median over chunk executions over K."""

from benchmark import torso_trace


def read(ctx):
    return torso_trace.scope_us(ctx, "torso.attn_full")
