"""Grouped matrix product over the experts a chip holds: rows sorted by
group, ``out[r] = x[r] @ w[g]`` for the rows ``r`` of group ``g``.

``x [M, K]``, ``w [G, K, N]``, ``sizes [G]`` int32 with ``sum(sizes) <= M``.
Rows past the groups belong to no group: **no implementation defines them**,
in the output or in the input gradient (the kernels leave the buffer as it
was, NaN included); a caller selects them away on both sides.

- ``megablox``: the Pallas grouped-matmul kernels jax ships
  (``jax.experimental.pallas.ops.tpu.megablox``: ``gmm`` forward and input
  gradient, ``tgmm`` weight gradient), whose grid visits only the row tiles
  the groups cover. Their own ``custom_vjp`` is not used: this one picks a
  tiling per product. On the v5e at the torso's sizes (8,078 rows of 32,768
  in 16 groups of ~512, K 2304, N 896; my chip run, PR 27) ``gmm`` takes
  0.37 ms at tiling (256, K, N) where XLA's own lowering of
  ``jax.lax.ragged_dot`` takes 1.34 ms, and the two gradients 0.97 ms
  against 2.83 ms. TPU only; the kernels take K and N in multiples of 128:
  widths a little off one (Nemotron-H's experts are 1,856 wide, 14.5 x 128)
  are zero-padded up to it round the call (``PAD_WITHIN`` of the width at
  most; the padded columns of ``w`` and of ``x`` multiply zeros and the
  padded outputs are cut off again, so the result and both gradients are
  the unpadded product's).
- ``ragged``: ``jax.lax.ragged_dot`` and its own gradients. Runs anywhere
  (the CPU tests, sizes the kernels' tiling refuses).
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

IMPLS = ("megablox", "ragged")
ROW_TILE = 256
# a product's [K, N] tile (bf16, double-buffered, beside a [256, N] float32
# accumulator) has to fit the kernel's share of VMEM; so has tgmm's float32
# [tk, tn] accumulator
TILE_ELEMS = 2304 * 896
TGMM_TILE_ELEMS = 768 * 896


def _backend():
    # the package's __init__ rebinds the name ``gmm`` to a function
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _divisor(n: int, cap: int) -> int:
    """``n`` if it fits, else its largest divisor that is a multiple of
    128 and at most ``cap``."""
    if n <= cap:
        return n
    for d in range(cap - cap % 128, 0, -128):
        if n % d == 0:
            return d
    raise ValueError(f"{n} has no divisor that is a multiple of 128")


def _tiling(k: int, n: int, elems: int) -> tuple:
    """Whole ``n`` where it fits beside a slice of ``k``, else whole ``k``
    beside a slice of ``n``."""
    if n <= k:
        return ROW_TILE, _divisor(k, max(128, elems // n)), n
    return ROW_TILE, k, _divisor(n, max(128, elems // k))


PAD_WITHIN = 1.125  # a width is padded to a multiple of 128 up to this much


def _padded(width: int) -> int:
    return -(-width // 128) * 128


def megablox_fits(k: int, n: int) -> bool:
    return all(_padded(width) <= PAD_WITHIN * width for width in (k, n))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _megablox(x, w, sizes, interpret: bool):
    _g, k, n = w.shape
    return _backend().gmm(x, w, sizes, x.dtype, _tiling(k, n, TILE_ELEMS),
                          interpret=interpret)


def _megablox_fwd(x, w, sizes, interpret):
    return _megablox(x, w, sizes, interpret), (x, w, sizes)


def _megablox_bwd(interpret, res, dy):
    x, w, sizes = res
    _g, k, n = w.shape
    mb = _backend()
    # dx = dy @ w[g]^T: the kernel reads w transposed, [N, K] a group
    dx = mb.gmm(dy, w, sizes, x.dtype, _tiling(n, k, TILE_ELEMS),
                transpose_rhs=True, interpret=interpret)
    dw = mb.tgmm(x.swapaxes(0, 1), dy, sizes, w.dtype,
                 _tiling(k, n, TGMM_TILE_ELEMS), interpret=interpret)
    return dx, dw, None


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def grouped_matmul(x, w, sizes, *, impl: str, interpret: bool = False):
    """``[M, N]`` in ``x``'s dtype; see the module docstring for the rows
    past the groups."""
    if impl not in IMPLS:
        raise ValueError(f"unknown grouped impl {impl!r}; one of {IMPLS}")
    if impl == "megablox":
        _g, k, n = w.shape
        more_k, more_n = _padded(k) - k, _padded(n) - n
        w, sizes = w.astype(x.dtype), sizes.astype(jnp.int32)
        if not (more_k or more_n):
            return _megablox(x, w, sizes, interpret)
        w = jnp.pad(w, ((0, 0), (0, more_k), (0, more_n)))
        x = jnp.pad(x, ((0, 0), (0, more_k)))
        return _megablox(x, w, sizes, interpret)[:, :n]
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes,
                              preferred_element_type=x.dtype)
