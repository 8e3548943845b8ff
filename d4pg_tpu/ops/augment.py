"""Image augmentation for pixel-observation learners: DrQ random shift.

The single highest-leverage ingredient in published pixel continuous
control at small data budgets (DrQ / RAD): pad the frame by ``pad``
pixels with edge replication, then take a per-sample random crop back to
the original size. Regularizes the conv encoder against the tiny-replay
overfitting that keeps greedy returns at the random-policy level (the
exact failure measured in ``docs/evidence/dmc-pixels/``).

Applied INSIDE the jit'd update (``learner/update.py``) on the sampled
batch — uint8 rows stay uint8 through the shift, so the replay ring and
the H2D path are untouched; both the critic and actor losses see the
same augmented view (the one-sample DrQ variant, M=K=1). The reference
has no pixel path at all (``models.py:15`` is state-only).

A fixed number of whole-batch operations whatever the batch size (a
one-hot selection along each spatial axis, applied as two batched
matrix products), elementwise in the batch axis, so the augmentation
shards over the batch axis under GSPMD like every other per-sample op.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import Array


def random_shift(key: Array, imgs: Array, pad: int = 4) -> Array:
    """Per-sample random shift of a [B, H, W, C] image batch.

    Each sample is edge-padded by ``pad`` on both spatial axes and
    re-cropped to [H, W] at an offset drawn uniformly from
    ``[0, 2*pad]^2`` — i.e. a shift of up to ``pad`` pixels in any
    direction, with edge-replicated fill. dtype-preserving (uint8 in,
    uint8 out).

    Offsets derive from per-sample ``fold_in(key, i)`` keys over a
    global iota rather than one batch-shaped ``randint(key, (B, 2))``:
    a single batch-shaped draw is NOT sharding-layout-invariant under
    GSPMD with the default (non-partitionable) threefry — each data
    shard would generate different bits than the global computation,
    so the {data, model}-mesh update would train on different crops
    than the single-device one (caught by the real-shape equivalence
    gate in tests/test_mesh_pixels.py). The fold_in form is elementwise
    in the batch axis, so partitioning preserves values exactly.

    The crop is separable and takes no per-sample slice: along each
    spatial axis, output position ``i`` of sample ``b`` reads input
    position ``clip(i + off[b] - pad, 0, n - 1)`` (the clip is the edge
    fill, so nothing is padded), written as a one-hot ``[B, n, n]``
    selection and applied by a batched matrix product. Each output is
    one non-zero term and a byte is exact in bfloat16, the MXU's native
    input, so the product is exact; any other dtype is shifted as its
    bytes (a float32 frame as four uint8 channels), which keeps one
    path and the exactness on every backend. A per-sample dynamic crop
    under ``vmap`` is a gather that the TPU runs as one one-row update
    per image: 290 ms for 512 frames of 84x84x9 against 0.85 ms for
    this form (PERF.md, PR 26)."""
    if imgs.ndim != 4:
        raise ValueError(f"random_shift expects [B, H, W, C], got "
                         f"{imgs.shape}")
    if pad < 1:
        return imgs
    b, h, w, _ = imgs.shape
    offs = jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(key, i), (2,), 0, 2 * pad + 1))(jnp.arange(b))

    def selection(off, n):
        src = jnp.clip(jnp.arange(n) + off[:, None] - pad, 0, n - 1)
        return jax.nn.one_hot(src, n, dtype=jnp.bfloat16)  # [B, out, in]

    out = imgs.view(jnp.uint8).astype(jnp.bfloat16)
    out = jnp.einsum("bih,bhwc->biwc", selection(offs[:, 0], h), out,
                     preferred_element_type=jnp.bfloat16)
    out = jnp.einsum("bjw,biwc->bijc", selection(offs[:, 1], w), out,
                     preferred_element_type=jnp.bfloat16)
    return out.astype(jnp.uint8).view(imgs.dtype)
