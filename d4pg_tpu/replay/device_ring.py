"""Device-resident replay storage: the transition ring lives in HBM.

TPU-native redesign of the replay data path (no reference equivalent — the
reference's buffers are per-process Python lists, ``replay_memory.py:14-19``,
``prioritized_replay_memory.py:164-222``): host<->device traffic, not
FLOPs, bounds a learner this small, and shipping every sampled
batch from host RAM costs O(batch bytes) per dispatch (25MB/chunk at
Humanoid sizes). With the ring in HBM the host keeps only the PER trees and
picks INDICES; the device gathers rows locally:

  - per-dispatch H2D drops to the [K, B] int32 index array (~16KB),
  - inserts stream the actor batches once (they must cross anyway),
  - the gathered chunk is already on device for the scanned update.

Two write paths:

  - ``write``: scatter by explicit index array (padded up to power-of-two
    buckets so XLA compiles a handful of scatter shapes; pad rows carry an
    out-of-bounds index and are dropped by ``mode='drop'``). Used for
    checkpoint restore and as the per-row reference path.
  - ``write_block``: the ingest fast path — ONE fixed-shape [block_rows]
    frame lands with a single dispatch built from two dynamic-slice
    updates (no scatter). The ring carries ``block_rows`` shadow rows past
    ``capacity``: the block is blended in contiguously at ``start`` (rows
    past the ring end spill into the shadow), then the spilled tail is
    mirrored into the ring head — wraparound as a second masked slice
    instead of a modular scatter. Partial blocks mask by ``n``; the shape
    is static, so steady-state ingest never recompiles.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from d4pg_tpu.replay.segment_tree import next_pow2 as _bucket
from d4pg_tpu.replay.uniform import TransitionBatch


def block_write(storage: TransitionBatch, frame: TransitionBatch,
                start, n, *, capacity: int, block_rows: int):
    """Pure two-slice block landing (see module docstring): blend a
    [block_rows] ``frame`` into the ring at ``start`` (first dynamic
    slice), then mirror the wrapped spill from the shadow tail into the
    head (second slice). ``n`` masks partial frames. Shared by the
    DeviceStore jit and the fused commit in ``replay/fused_buffer.py``
    (which fuses it with the PER tree insert into ONE dispatch)."""
    import jax
    import jax.numpy as jnp

    row = jax.lax.iota(jnp.int32, block_rows)
    wrapped = jnp.maximum(start + n - capacity, 0)

    def upd(arr, val):
        mask = (row < n).reshape((block_rows,) + (1,) * (arr.ndim - 1))
        cur = jax.lax.dynamic_slice_in_dim(arr, start, block_rows)
        arr = jax.lax.dynamic_update_slice_in_dim(
            arr, jnp.where(mask, val.astype(arr.dtype), cur), start, 0)
        # wraparound: rows that spilled past `capacity` also belong at the
        # ring head — static-position tail/head slices, so the whole write
        # is two dynamic_update_slices, no scatter
        tail = jax.lax.dynamic_slice_in_dim(arr, capacity, block_rows)
        head = jax.lax.dynamic_slice_in_dim(arr, 0, block_rows)
        hmask = (row < wrapped).reshape((block_rows,) + (1,) * (arr.ndim - 1))
        return jax.lax.dynamic_update_slice_in_dim(
            arr, jnp.where(hmask, tail, head), 0, 0)

    return TransitionBatch(*[upd(arr, val) for arr, val in zip(storage, frame)])


class DeviceStore:
    """Fixed-capacity transition storage on an accelerator device.

    Same write/read interface as the host numpy storage inside
    ``ReplayBuffer``; ``read`` accepts [B] or [K, B] index arrays and
    returns device arrays (zero host copies). ``block_rows > 0``
    additionally compiles the two-slice block writer (and allocates that
    many shadow rows — consumers must index only ``[0, capacity)``, which
    every sampler already does).
    """

    def __init__(
        self,
        capacity: int,
        obs_shape: tuple,
        act_dim: int,
        obs_dtype,
        device=None,
        block_rows: int = 0,
    ):
        import jax
        import jax.numpy as jnp

        self.capacity = int(capacity)
        self.block_rows = int(block_rows)
        if self.block_rows > self.capacity:
            raise ValueError(
                f"block_rows {block_rows} exceeds capacity {capacity}")
        # shadow rows past the ring end absorb a block's wraparound spill
        # (mirrored into the head by write_block); index `rows` is the one
        # guaranteed-out-of-bounds scatter-drop index either way
        rows = self.capacity + self.block_rows
        self._rows = rows
        storage = TransitionBatch(
            obs=jnp.zeros((rows, *obs_shape), obs_dtype),
            action=jnp.zeros((rows, act_dim), jnp.float32),
            reward=jnp.zeros((rows,), jnp.float32),
            next_obs=jnp.zeros((rows, *obs_shape), obs_dtype),
            done=jnp.zeros((rows,), jnp.float32),
            discount=jnp.zeros((rows,), jnp.float32),
        )
        self._storage = (
            jax.device_put(storage, device) if device is not None else
            jax.device_put(storage)
        )

        @partial(jax.jit, donate_argnums=(0,))
        def _insert(storage, idx, batch):
            return TransitionBatch(*[
                arr.at[idx].set(val.astype(arr.dtype), mode="drop")
                for arr, val in zip(storage, batch)
            ])

        @jax.jit
        def _gather(storage, idx):
            return TransitionBatch(*[arr[idx] for arr in storage])

        self._insert = _insert
        self._gather = _gather
        self._write_block = (
            self._make_write_block() if self.block_rows else None)

    def _make_write_block(self):
        import jax

        return jax.jit(
            partial(block_write, capacity=self.capacity,
                    block_rows=self.block_rows),
            donate_argnums=(0,))

    @property
    def arrays(self) -> TransitionBatch:
        """The raw [capacity (+ shadow), ...] device arrays (read-only
        input to the fused learner path, ``learner/fused.py``; samplers
        index only ``[0, capacity)``)."""
        return self._storage

    def write(self, idx: np.ndarray, batch: TransitionBatch) -> None:
        n = len(idx)
        m = _bucket(n)
        if m != n:
            pad = m - n
            # pad index == total rows -> out of bounds -> dropped
            idx = np.concatenate(
                [idx, np.full(pad, self._rows, idx.dtype)])
            batch = TransitionBatch(*[
                np.concatenate([np.asarray(v),
                                np.zeros((pad, *np.asarray(v).shape[1:]),
                                         np.asarray(v).dtype)])
                for v in batch
            ])
        self._storage = self._insert(
            self._storage, np.asarray(idx, np.int32), batch)

    def write_block(self, start: int, frame: TransitionBatch, n: int) -> None:
        """Land ``n`` valid rows of a fixed-shape [block_rows] ``frame``
        at ring position ``start`` in ONE dispatch (see module docstring).
        ``frame`` may already live on device (staged by an earlier
        ``device_put``) — the dispatch then moves no row bytes at all."""
        if self._write_block is None:
            raise RuntimeError("DeviceStore built without block_rows")
        self._storage = self._write_block(
            self._storage, frame, np.int32(start), np.int32(n))

    def swap_arrays(self, storage: TransitionBatch) -> None:
        """Adopt updated storage handles (the fused commit in
        ``replay/fused_buffer.py`` runs the block write inside its own
        dispatch, fused with the tree insert, and hands the result back)."""
        self._storage = storage

    def read(self, idx: np.ndarray) -> TransitionBatch:
        """Gather rows on device; idx [B] or [K, B] (host or device ints)."""
        return self._gather(self._storage, np.asarray(idx, np.int32))
