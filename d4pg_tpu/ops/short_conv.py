"""LFM2's gated short convolution: two linear gates round a depthwise
causal convolution of a few taps a channel.

``bcu [T, 3 C]`` holds the three chunks of the layer's input projection in
that order (``b``, ``c``, ``u``; Hugging Face's ``Lfm2ShortConv``), ``taps
[C, L]`` one kernel a channel, no bias, no activation::

    g[t] = b[t] * u[t]
    s[t] = sum_j taps[:, j] * g[t - (L - 1 - j)]        (g[< 0] = 0)
    out[t] = c[t] * s[t]

Position ``t`` reads ``g`` at ``t - L + 1 ... t`` and nothing later. One
form, plain ``jax.numpy``: ``L`` shifted multiply-adds in float32 on inputs
of any dtype, which XLA fuses into one pass over ``bcu`` on the TPU and the
CPU alike; its gradients are autodiff's (the input gradient is the same
shifts the other way, the taps' gradient a reduction over positions). The
forms that lost to it on the chip are in PERF.md (PR 34).
"""

from __future__ import annotations

import jax.numpy as jnp


def shifted(g, back: int):
    """``g [T, C]`` delayed by ``back`` positions: row ``t`` holds
    ``g[t - back]``, the first ``back`` rows zeros."""
    if back == 0:
        return g
    return jnp.pad(g, ((back, 0), (0, 0)))[:g.shape[0]]


def depthwise_causal(g, taps, bias=None):
    """``s[t] = sum_j taps[:, j] * g[t - (L - 1 - j)]`` in float32 on ``g [T,
    C]`` of any dtype: the convolution alone (Qwen3-Next's Gated DeltaNet
    puts a SiLU behind it and no gates round it), with ``bias [C]`` added at
    every position (Nemotron-H's Mamba-2 mixer)."""
    g, taps = g.astype(jnp.float32), taps.astype(jnp.float32)
    n_taps = taps.shape[1]
    s = sum(taps[:, j] * shifted(g, n_taps - 1 - j) for j in range(n_taps))
    return s if bias is None else s + bias


def gated_short_conv(bcu, taps):
    """``[T, C]`` in ``bcu``'s dtype (module docstring)."""
    b, c, u = (x.astype(jnp.float32) for x in jnp.split(bcu, 3, axis=-1))
    return (c * depthwise_causal(b * u, taps)).astype(bcu.dtype)
