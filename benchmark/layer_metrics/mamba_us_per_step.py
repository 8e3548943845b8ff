"""Device time a gradient step spends in the Mamba-2 mixers without their
recurrence (RMSNorm, ``in_proj``, the taps with their bias and the SiLU, the
gated group norm, ``out_proj``; the ``torso.mamba`` scope), forward and
backward, in microseconds."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.scope_us(ctx, "torso.mamba")
