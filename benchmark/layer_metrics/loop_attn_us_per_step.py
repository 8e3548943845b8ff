"""Device time a gradient step spends under ``torso.attn_full`` in the looped
Ouro torso cell (both norms, the projections, RoPE and the attention of every
layer application; all passes)."""

from benchmark import loop_trace


def read(ctx):
    return loop_trace.scope_us(ctx, "torso.attn_full")
