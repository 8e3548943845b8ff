"""The models' input cast, written so that it stays where it is written.

XLA rewrites ``convert(gather(ring))`` as ``gather(convert(ring))`` and
lifts the convert of the whole ring out of the fused chunk's ``while``:
4.7 GB narrowed once a dispatch to serve 256 rows a step (PERF.md, PR 31;
an optimisation barrier on the gathered batch does not stop it). A
``reduce_precision`` between the gather and the convert does: the
compiler moves neither through the other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def to_compute(x: jax.Array, dtype) -> jax.Array:
    """``x`` in the compute dtype, bit for bit ``x.astype(dtype)``.

    A float narrowed to a float of the same exponent width (float32 to
    bfloat16) is first rounded to the target's mantissa bits in its own
    type (round to nearest even, as the convert rounds), so the convert
    that follows is exact. ``x`` itself when it already has the dtype; a
    plain cast for anything else (``uint8`` frames, widening, and
    float16, whose subnormals ``reduce_precision`` would flush)."""
    dtype = jnp.dtype(dtype)
    if x.dtype == dtype:
        return x
    if (jnp.issubdtype(x.dtype, jnp.floating)
            and jnp.issubdtype(dtype, jnp.floating)):
        src, dst = jnp.finfo(x.dtype), jnp.finfo(dtype)
        if dst.nexp == src.nexp and dst.nmant < src.nmant:
            x = jax.lax.reduce_precision(x, dst.nexp, dst.nmant)
    return x.astype(dtype)
