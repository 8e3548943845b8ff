"""Device time a gradient step spends under ``torso.attn_full`` (the full
causal layer of the Trinity-Mini torso, without rotary embedding: norm, five
projections, the norms on the heads, the kernel, the gate, the output
projection and its post-norm; all passes), in microseconds."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.scope_us(ctx, "torso.attn_full")
