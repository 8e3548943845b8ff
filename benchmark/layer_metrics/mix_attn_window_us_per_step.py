"""Device time a gradient step spends under ``torso.attn_window`` (the
sliding-window layers of the Trinity-Mini torso: norm, five projections, the
norms on the heads, the rotation, the kernel under the 2,048 window, the gate,
the output projection and its post-norm; all passes), in microseconds."""

from benchmark import mix_trace


def read(ctx):
    return mix_trace.scope_us(ctx, "torso.attn_window")
