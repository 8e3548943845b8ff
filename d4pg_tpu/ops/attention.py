"""Causal and sliding-window attention over long sequences, grouped-query.

``[B, heads, T, T]`` scores cannot be materialised at the torso's sizes
(4 x 32 x 4096 x 4096 float32 is 8.6 GB), so both implementations work a
block of queries at a time and never touch a block of keys the mask
empties, forward or backward:

- ``splash``: the Pallas splash-attention kernel jax ships
  (``jax.experimental.pallas.ops.tpu.splash_attention``), multi-query form,
  one call per key/value head with its group of query heads. The mask is
  static (``LocalMask``), so the kernel's grids visit kept blocks only; its
  backward is the kernel's own. TPU only: blocks are multiples of 128.
- ``blockwise``: plain ``jax.numpy``. A static loop over blocks of queries;
  each takes the static slice of keys its mask keeps and is rematerialised
  in the backward pass, so neither pass holds more than one block's scores.
  Runs anywhere (the CPU tests, sizes the kernel's tiling refuses).

Shapes: ``q [B, Hkv, G, T, D]`` (``G`` query heads share a key/value head),
``k``, ``v`` ``[B, Hkv, T, D]``. ``q`` comes already scaled. Position ``t``
sees ``s <= t`` and, with ``window``, only ``s > t - window``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

IMPLS = ("splash", "blockwise")


def _block(q, k, v, *, start: int, lo: int, window: int | None):
    """One block of queries (positions ``start``...) against keys ``lo``...:
    float32 scores and softmax, the product with ``v`` in ``v``'s dtype."""
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    t = start + jnp.arange(q.shape[-2])[:, None]
    pos = lo + jnp.arange(k.shape[-2])[None, :]
    keep = pos <= t
    if window is not None:
        keep &= pos > t - window
    s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def blockwise_attention(q, k, v, *, window: int | None,
                        block: int | None = None):
    """``block`` queries at a time: 512, or a quarter of a short
    sequence."""
    t_len = q.shape[-2]
    block = block or max(1, min(512, t_len // 4))
    out = []
    for start in range(0, t_len, block):
        end = min(start + block, t_len)
        lo = 0 if window is None else max(0, start - window + 1)
        fn = jax.checkpoint(functools.partial(
            _block, start=start, lo=lo, window=window))
        out.append(fn(q[..., start:end, :], k[..., lo:end, :],
                      v[..., lo:end, :]))
    return jnp.concatenate(out, axis=-2)


@functools.lru_cache(maxsize=None)
def _splash_kernel(t_len: int, group: int, window: int | None,
                   interpret: bool):
    """The kernel for one key/value head and its ``group`` query heads.
    Block sizes from the v5e at T 4096, 8 query heads of 128 (my chip run,
    PR 27; forward / forward and backward, ms): under a 1,024 window blocks
    of 512 read 1.02 / 3.33 (1,024: 1.14 / 3.76; 256: 1.92 / 5.64) and the
    fused backward changes nothing; full causal reads 1.45 / 4.29 at
    1,024 x 1,024 with 512-wide compute and the fused backward, 1.78 / 5.95
    at 512 unfused. At T 8192 and 8 key/value heads of 64 with 4 queries
    each (my chip run, PR 34; all 8 heads, ms) full causal reads 5.19 /
    15.94 as it is, 5.37 / 16.41 with q, k, v zero-padded to 128 and the
    blockwise form 240.9 / 268.9: heads of 64 go to the kernel as they are
    (it fills half the lanes: the same time as 128-wide heads with twice
    the products)."""
    from jax.experimental.pallas.ops.tpu import splash_attention as sa

    left = None if window is None else window - 1
    mask = sa.MultiHeadMask([sa.LocalMask((t_len, t_len), (left, 0), 0)
                             for _ in range(group)])
    if window is None:
        big, small = min(1024, t_len), min(512, t_len)
        sizes = sa.BlockSizes(
            block_q=big, block_kv=big, block_kv_compute=small,
            block_q_dkv=big, block_kv_dkv=big, block_kv_dkv_compute=small,
            use_fused_bwd_kernel=True)
    else:
        b = min(512, t_len)
        sizes = sa.BlockSizes(
            block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
            block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b,
            block_kv_dq=b)
    # the mask tables are made as constants, outside any trace in progress
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mqa_single_device(
            mask=mask, block_sizes=sizes, interpret=interpret)


def splash_attention(q, k, v, *, window: int | None,
                     interpret: bool = False):
    _b, _h, group, t_len, _d = q.shape
    kernel = _splash_kernel(t_len, group, window, interpret)
    return jax.vmap(jax.vmap(kernel))(q, k, v)


def splash_fits(t_len: int, head_dim: int) -> bool:
    """Whether the kernel's tiling takes these sizes: heads of 128 fill
    the lanes, heads of 64 half of them (the kernel pads its own tiles)."""
    return t_len % 128 == 0 and head_dim % 64 == 0


def causal_attention(q, k, v, *, window: int | None, impl: str,
                     block: int | None = None):
    """``block``: the blockwise form's query block (the kernel has its
    own sizes)."""
    if impl not in IMPLS:
        raise ValueError(f"unknown attention impl {impl!r}; one of {IMPLS}")
    if window is not None and window >= q.shape[-2]:
        window = None  # the window never cuts: plain causal
    if impl == "splash":
        return splash_attention(q, k, v, window=window)
    return blockwise_attention(q, k, v, window=window, block=block)
