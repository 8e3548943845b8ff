"""Device time a gradient step spends in the Mamba-2 recurrence itself (the
softplus, the decay, the chunked scan of ``ops/ssd.py``; the
``torso.ssd_scan`` scope), forward and backward, in microseconds."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.scope_us(ctx, "torso.ssd_scan")
