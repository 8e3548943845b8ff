"""Percent of a Mamba state that a token keeps: the mean of ``exp(dt A)`` over
heads, tokens, sequences, blocks and steps, from the traced window's last
chunk metrics (``ssd_kept [K, Mamba blocks]``). 100 never forgets, 0 has no
memory; the seeding (A ~ U(1, 16), dt log-uniform on [1e-3, 1e-1] behind a
softplus of a projection that moves it) gives a band of 60 to 95."""

from benchmark import ssm_trace


def read(ctx):
    return ssm_trace.kept_share(ctx)
