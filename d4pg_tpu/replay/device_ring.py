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

Layout (PR 31, 33): a field is stored the way its row gather reads it,
whatever the compiler would choose. ``ring_layout`` is the rule, from the
field's static shape and dtype and the platform of the store's device.
The TPU's compact layout for ``f32[2101248, 376]`` puts the ROWS on the
lanes (nothing to pad; rows-major pads 376 to 384), and for
``u8[40256, 84, 84, 9]`` the FRAMES (``{0,2,3,1}``); a row gather from
either is strided, and the fused chunk answered with a re-laid copy of
the whole field once a dispatch (14.4 of the MLP chunk's 26.7 ms, 31.9
of the pixel chunk's 91.5; PERF.md). So a wide rank-2 float field is
pinned rows-major (a row's values along the lanes), and on a TPU a
rank-4 ``uint8`` field ``[rows, H, W, C]`` is pinned ``(0, 3, 1, 2)``:
a frame contiguous, its channels as planes, H on the sublanes and W on
the lanes, which is the layout the compiler itself gave the gather's
operand. Every program that returns the ring returns it in the store's
formats, so donation still aliases. Those programs (the writers, the
re-layout, the fused commit) are compiled outside the persistent compile
cache where a field is pinned (``io/profiling.fresh_compile`` says why):
it costs each start their compiles, a fraction of a second together.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from d4pg_tpu.io.profiling import FreshProgram, fresh_compile
from d4pg_tpu.obs import trace as obs_trace
from d4pg_tpu.replay.segment_tree import next_pow2 as _bucket
from d4pg_tpu.replay.uniform import TransitionBatch

_LANES = 128


def ring_layout(shape: tuple, dtype, platform: str) -> tuple | None:
    """``major_to_minor`` a ring field of this static shape is pinned to on
    a device of ``platform``, or ``None`` for the compiler's own layout.

    Rows-major for a rank-2 float field whose width pads at most an eighth
    on the 128 lanes (376 -> 384: yes; a 17-wide action would pad 7.5x:
    no). Scalars a row keep the compiler's layout.

    ``(0, 3, 1, 2)`` (XLA's ``{2,1,3,0}``) for a rank-4 ``uint8`` field
    ``[rows, H, W, C]`` on a TPU: rows-major would put the 9 channels on
    the lanes (14x). The pin is a statement about the TPU's ``(8,128)(4,1)``
    tiles, so the platform is part of the rule's input: it is not the
    CPU's default, and pinned there every pixel store would transpose its
    ring and compile outside the cache for a layout no CPU user wants.
    It pads H to a multiple of 8 and W of 128 (84 x 84: 1.60x, 4.08 GB a
    field at 40,256 rows), and there is no padding threshold as for float
    rows: the resident padded field replaces a transient padded copy of
    the same size BESIDE the compact field, so the pin never raises a
    chunk's footprint, whatever H, W and C are. The pixel cell's chunk
    compiled for a v5e holds 5.51 GB of arguments and 8.57 GB of
    temporaries unpinned, 8.31 GB and 0.42 GB pinned
    (``tests/test_torso_v5e_compile.py``)."""
    dtype = np.dtype(dtype)
    if len(shape) == 4 and dtype == np.uint8:
        return (0, 3, 1, 2) if platform == "tpu" else None
    if len(shape) != 2 or not np.issubdtype(dtype, np.floating):
        return None
    w = int(shape[1])
    return (0, 1) if -(-w // _LANES) * _LANES <= 1.125 * w else None


def ring_specs(rows: int, obs_shape: tuple, act_dim: int,
               obs_dtype) -> TransitionBatch:
    """``(shape, dtype)`` of each ring field."""
    f32 = np.dtype(np.float32)
    return TransitionBatch(
        obs=((rows, *obs_shape), np.dtype(obs_dtype)),
        action=((rows, act_dim), f32),
        reward=((rows,), f32),
        next_obs=((rows, *obs_shape), np.dtype(obs_dtype)),
        done=((rows,), f32),
        discount=((rows,), f32),
    )


def ring_formats(specs: TransitionBatch, home) -> TransitionBatch:
    """The ``Format`` each field is pinned to on the one-device sharding
    ``home`` (a layout is honoured only on a committed array, so a pinned
    field names its device, and the rule reads that device's platform),
    ``None`` where ``ring_layout`` leaves the compiler's layout."""
    from jax.experimental.layout import Format, Layout

    platform = next(iter(home.device_set)).platform
    return TransitionBatch(*[
        None if mtm is None else Format(Layout(major_to_minor=mtm), home)
        for mtm in (ring_layout(*spec, platform) for spec in specs)])


def ring_program(fn, formats: TransitionBatch):
    """Jitted ``fn``, which returns the ring in ``formats``, as it is to be
    called: through ``FreshProgram`` where a field is pinned, as it is
    where none is."""
    return FreshProgram(fn) if any(f is not None for f in formats) else fn


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

    Each field has one format (``formats``: a pinned
    ``jax.experimental.layout.Format`` where ``ring_layout`` names one,
    else ``None``). A new store's ring is as the allocator lays it out, so
    that a program that knows nothing of the formats can fill it in place
    (donated in, the same layout out: the benchmark's seeded fill, which
    has no room for a second ring). It is in its formats from the first
    write of the store's own (``pinned``) or the first ``swap_arrays``,
    and for life after: returned in them by ``_insert``, ``_write_block``
    and the fused commit (``formats`` are their ``out_shardings``). Where
    every pinned layout is the device's default anyway (the CPU: rows-major
    is, and frames are pinned on a TPU alone) nothing is ever re-laid.
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

        from d4pg_tpu.parallel import partition

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
        specs = ring_specs(rows, tuple(obs_shape), act_dim, obs_dtype)
        # Every field is committed to the store's device: a layout is
        # honoured only on a committed array, and what a program returns
        # is committed once an argument is, so a ring committed in part
        # would change a program's argument signature (one more compile)
        # at its second call.
        self.home = partition.one_device(device)
        self.formats = ring_formats(specs, self.home)
        self._storage = jax.device_put(TransitionBatch(*[
            jnp.zeros(shape, dtype) for shape, dtype in specs]), self.home)
        self._pinned = False  # in its formats: see ``pinned``

        @partial(jax.jit, donate_argnums=(0,), out_shardings=self.formats)
        def _insert(storage, idx, batch):
            return TransitionBatch(*[
                arr.at[idx].set(val.astype(arr.dtype), mode="drop")
                for arr, val in zip(storage, batch)
            ])

        @jax.jit
        def _gather(storage, idx):
            return TransitionBatch(*[arr[idx] for arr in storage])

        self._insert = ring_program(_insert, self.formats)
        self._gather = _gather
        self._write_block = (
            self._make_write_block() if self.block_rows else None)

    def _make_write_block(self):
        import jax

        return ring_program(jax.jit(
            partial(block_write, capacity=self.capacity,
                    block_rows=self.block_rows),
            donate_argnums=(0,), out_shardings=self.formats), self.formats)

    @property
    def arrays(self) -> TransitionBatch:
        """The raw [capacity (+ shadow), ...] device arrays (read-only
        input to the fused learner path, ``learner/fused.py``; samplers
        index only ``[0, capacity)``)."""
        return self._storage

    def pinned(self) -> TransitionBatch:
        """The ring in the store's formats, for a program that writes it
        (re-laid now if it was never written: twice ~10 ms at 2 M
        Humanoid rows)."""
        if not self._pinned:
            self.swap_arrays(self._storage)
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
            self.pinned(), np.asarray(idx, np.int32), batch)

    def write_block(self, start: int, frame: TransitionBatch, n: int) -> None:
        """Land ``n`` valid rows of a fixed-shape [block_rows] ``frame``
        at ring position ``start`` in ONE dispatch (see module docstring).
        ``frame`` may already live on device (staged by an earlier
        ``device_put``) — the dispatch then moves no row bytes at all."""
        if self._write_block is None:
            raise RuntimeError("DeviceStore built without block_rows")
        self._storage = self._write_block(
            self.pinned(), frame, np.int32(start), np.int32(n))

    def swap_arrays(self, storage: TransitionBatch) -> None:
        """Adopt updated storage handles. The fused commit in
        ``replay/fused_buffer.py`` runs the block write inside its own
        dispatch, fused with the tree insert, and hands the result back in
        the store's formats: nothing happens to it here. This is also the
        one door a foreign layout can come through (a ring filled by a
        program that does not know the formats, a restored checkpoint): a
        field that is not in the store's format is re-laid, one field at a
        time and with its source donated (a second whole ring does not fit
        beside the first), under a ``ring.relayout`` span."""
        import jax

        fields = list(storage)
        for i, (name, fmt) in enumerate(zip(storage._fields, self.formats)):
            arr = fields[i]
            if fmt is not None and (arr.format.layout.major_to_minor
                                    != fmt.layout.major_to_minor):
                with obs_trace.span("ring.relayout", field=name,
                                    bytes=int(arr.nbytes)), fresh_compile():
                    # (a field at a time, never a row: six at most)
                    fields[i] = jax.block_until_ready(jax.device_put(  # jaxlint: disable=device-put-in-loop
                        arr, fmt, donate=True))
                    # a donation that cannot alias (another layout) is
                    # dropped and the source lives on with its holder:
                    # release it, or the next field finds no room
                    if not arr.is_deleted():
                        arr.delete()
            elif not arr.committed:
                # the same buffer, committed like the rest (once a field)
                fields[i] = jax.device_put(arr, fmt or self.home)  # jaxlint: disable=device-put-in-loop
        self._storage = TransitionBatch(*fields)
        self._pinned = True

    def read(self, idx: np.ndarray) -> TransitionBatch:
        """Gather rows on device; idx [B] or [K, B] (host or device ints)."""
        return self._gather(self._storage, np.asarray(idx, np.int32))
