"""Device time a gradient step spends in the attention block without rotary
embedding (32 query heads on 2 key/value heads of 128; the
``torso.attn_full`` scope), forward and backward, in microseconds."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.scope_us(ctx, "torso.attn_full")
