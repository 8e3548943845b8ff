"""Replay buffer for the fused device path: ring + PER trees in HBM.

Companion to ``learner/fused.py``. Ownership model (the part that makes
cross-thread donation safe): ``add`` — called from the ReplayService
drain thread under the buffer lock — only STAGES host rows; every device
mutation (ring write, tree insert, and the fused chunk's tree write-back)
happens on the learner thread, which is the single owner of the
``trees``/storage handles. Staged rows take effect between chunks — the
same semantics the host-PER path gets from its buffer lock, without the
learner ever blocking on actor ingest.

Ingest fast path (the batched block drain; docs/architecture.md "Ingest
plane"): ``add`` copies rows column-major into a PREALLOCATED host
staging ring (no per-drain ``np.concatenate``, no per-row device work).
The learner moves a block with exactly two calls:

  - ``stage_block()`` — ONE ``jax.device_put`` of a fixed-shape
    [block_rows] frame (the H2D transfer; async under dispatch, so it
    overlaps the in-flight fused chunk's compute),
  - ``commit_staged()`` — ONE jitted dispatch fusing the two-slice ring
    write (``device_ring.block_write``) with the PER tree insert at
    ``max_priority ** alpha``; storage and trees are donated.

``drain()`` loops stage+commit until the staging ring is empty (cycle
boundaries, checkpointing); the overlapped schedule in
``learner/pipeline.IngestOverlap`` interleaves the two calls with fused
chunks so steady state pays ≤ 1 explicit H2D per chunk. ``drain_per_row``
keeps the old one-dispatch-per-row path as the measured baseline and the
bitwise-equivalence oracle (tests/test_ingest.py).

The generation guard the host path needs (``prioritized.py`` — a sampled
slot overwritten before its priority lands) is structurally unnecessary
here: priorities are written INSIDE the chunk, and inserts only happen
between chunks on the same thread.

Reference scope covered: ``prioritized_replay_memory.py:224-335``
(priority lifecycle) + ``replay_memory.py:14-80`` (ring), relocated to
the accelerator.
"""

from __future__ import annotations

import time
from collections import deque
from functools import partial

import numpy as np

from d4pg_tpu.obs.flight import record_event
from d4pg_tpu.obs import trace as obs_trace
from d4pg_tpu.obs.registry import REGISTRY
from d4pg_tpu.obs.trace import RECORDER as _tracer
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.device_ring import (DeviceStore, block_write,
                                         ring_program)
from d4pg_tpu.replay.uniform import TransitionBatch


class HostStagingRing:
    """Preallocated column-major host staging for fixed-shape block frames.

    One contiguous buffer per transition field, ``n_blocks * block_rows``
    rows plus a ``block_rows`` scratch tail so the next frame is ALWAYS a
    contiguous in-bounds [block_rows] view (a partial or boundary-capped
    frame just carries a smaller valid count ``n``; rows past ``n`` are
    stale scratch masked out on device). ``push`` is slice assignment —
    the only host copy a row ever pays — and ``frame`` is zero-copy.

    Bounded like the list staging it replaces: when producers outrun the
    learner by more than the ring, the OLDEST staged rows are dropped
    (they would only be overwritten by the next drains anyway) — and
    counted: ``fused.rows_dropped`` in the registry, a ``staging_drop``
    event in the flight ring.

    A row's POSITION is its 1-based index in the stream of rows pushed
    (``written`` after its push); ``consumed`` is the position of the last
    row popped or dropped, so the next frame holds positions ``consumed +
    1 .. consumed + n``. Each push notes ``(written, time.monotonic())``
    so the stager can say how long the oldest row of a frame waited
    (``oldest_push``).

    Reuse discipline: a popped frame's rows are rewritten only after the
    write pointer laps the ring (≥ ``(n_blocks - 1) * block_rows`` newer
    rows), which keeps them intact for the duration of the in-flight
    ``device_put`` even on backends that complete H2D asynchronously.
    """

    def __init__(self, specs, block_rows: int, n_blocks: int):
        self.block_rows = int(block_rows)
        self.n_blocks = max(2, int(n_blocks))
        self.size = self.block_rows * self.n_blocks
        self._arrays = [
            np.zeros((self.size + self.block_rows, *shape), dtype)
            for shape, dtype in specs
        ]
        self._r = 0  # absolute rows consumed
        self._w = 0  # absolute rows written
        self._pushed: deque = deque()  # (self._w after the push, its time)
        self.dropped = 0  # rows dropped so far

    def __len__(self) -> int:
        return self._w - self._r

    @property
    def written(self) -> int:
        """Position of the newest row pushed."""
        return self._w

    @property
    def consumed(self) -> int:
        """Position of the last row popped or dropped."""
        return self._r

    def oldest_push(self) -> float | None:
        """``time.monotonic()`` of the push that wrote the oldest pending row
        (``None`` when empty)."""
        pushed = self._pushed
        while pushed and pushed[0][0] <= self._r:
            pushed.popleft()
        return pushed[0][1] if pushed else None

    def push(self, batch: TransitionBatch, at: float | None = None) -> int:
        """``at``: when the rows were first staged, for a push that only
        moves them on (the multi-ring merge); now otherwise. Returns the
        rows dropped to admit these."""
        n = batch.obs.shape[0]
        dropped = max(0, n - self.size)
        if dropped:  # keep only the newest ring-full
            batch = TransitionBatch(*[np.asarray(v)[-self.size:]
                                      for v in batch])
            n = self.size
        off = self._w % self.size
        first = min(n, self.size - off)
        for dst, src in zip(self._arrays, batch):
            src = np.asarray(src)
            dst[off:off + first] = src[:first]
            if first < n:
                dst[:n - first] = src[first:]
        self._w += n
        if self._w - self._r > self.size:
            dropped += self._w - self.size - self._r
            self._r = self._w - self.size  # drop oldest
        self.oldest_push()  # forget the pushes whose rows are all gone
        self._pushed.append(
            (self._w, time.monotonic() if at is None else at))
        if dropped:
            self.dropped += dropped
            REGISTRY.counter("fused.rows_dropped").inc(dropped)
            record_event("staging_drop", rows=dropped)
        return dropped

    def frame(self) -> tuple[TransitionBatch, int]:
        """Next pending frame as fixed-shape [block_rows] views + its
        valid row count (0 when empty). Capped at the ring boundary so
        the views stay contiguous."""
        off = self._r % self.size
        n = min(self._w - self._r, self.block_rows, self.size - off)
        views = TransitionBatch(*[a[off:off + self.block_rows]
                                  for a in self._arrays])
        return views, n

    def pop(self, n: int) -> None:
        self._r += n

    def take(self, n: int) -> list[TransitionBatch]:
        """Pop the ``n`` oldest staged rows as one or two per-field view
        batches (two when the run wraps the ring boundary). Zero-copy;
        the views are only valid until the writer laps the ring — the
        multi-ring merge copies them onward immediately
        (``staging.MultiRingStaging``)."""
        n = min(n, len(self))
        if n <= 0:
            return []
        off = self._r % self.size
        first = min(n, self.size - off)
        out = [TransitionBatch(*[a[off:off + first] for a in self._arrays])]
        if first < n:
            out.append(TransitionBatch(*[a[:n - first]
                                         for a in self._arrays]))
        self._r += n
        return out


def make_commit(capacity: int, block: int, alpha: float, ring, *,
                prioritized: bool = True, gen_tracked: bool = False):
    """The jitted block commit behind ``FusedDeviceReplay.commit_staged``:
    the two-slice ring write fused with the PER tree insert, ring and
    trees donated. ``ring`` is the store's ``formats``: the ring goes out
    in the formats it came in (device_ring.py "Layout"), so the donation
    aliases and no commit re-lays it."""
    import jax
    import jax.numpy as jnp

    write = partial(block_write, capacity=capacity, block_rows=block)

    # Every variant names its two phases (metadata only); a trace
    # reader splits the commit program's device time by them.
    if not prioritized:
        @partial(jax.jit, donate_argnums=(0,), out_shardings=ring)
        def commit_uniform(storage, frame, start, n):
            with jax.named_scope("ingest.ring_write"):
                return write(storage, frame, start, n)

        return commit_uniform

    if gen_tracked:
        from d4pg_tpu.replay.segment_tree import next_pow2

        # pads park at the TREE capacity (>= ring capacity): dropped
        # by set_leaves' idx < capacity guard AND out of bounds for
        # the [capacity] generation array, so one pad value silences
        # both scatters. (The non-tracked path's repeat-the-first-
        # slot pad would bump that slot's generation spuriously.)
        padcap = next_pow2(capacity)

        @partial(jax.jit, donate_argnums=(0, 1, 2),
                 out_shardings=(ring, None, None))
        def commit_tracked(storage, trees, gen, frame, start, n,
                           p_ins, max_pri):
            with jax.named_scope("ingest.ring_write"):
                storage = write(storage, frame, start, n)
            with jax.named_scope("ingest.tree_insert"):
                row = jax.lax.iota(jnp.int32, block)
                idx = jnp.where(row < n, (start + row) % capacity,
                                padcap)
                # p_ins is max_priority ** alpha computed on the HOST
                # (float64 pow, cast f32) — see the gen_tracked note
                # in FusedDeviceReplay.__init__; the trees only ever
                # see host-rounded values
                trees = dper.set_leaves(
                    trees, idx, jnp.full((block,), p_ins, jnp.float32))
                trees = trees._replace(max_priority=max_pri)
                gen = gen.at[idx].add(1, mode="drop")
            return storage, trees, gen

        return commit_tracked

    @partial(jax.jit, donate_argnums=(0, 1), out_shardings=(ring, None))
    def commit(storage, trees, frame, start, n):
        with jax.named_scope("ingest.ring_write"):
            storage = write(storage, frame, start, n)
        with jax.named_scope("ingest.tree_insert"):
            row = jax.lax.iota(jnp.int32, block)
            # pad rows repeat the first live slot: duplicate writes of
            # the same value are harmless to the trees (see
            # device_per.insert)
            idx = jnp.where(row < n, (start + row) % capacity,
                            start % capacity)
            trees = dper.insert(trees, idx, alpha)
        return storage, trees

    return commit


class FusedDeviceReplay:
    """Fixed-capacity device ring + (optionally) device PER trees."""

    def __init__(
        self,
        capacity: int,
        obs_dim: int | tuple,
        act_dim: int,
        alpha: float = 0.6,
        prioritized: bool = True,
        obs_dtype=None,
        device=None,
        block_rows: int | None = None,
        staging_blocks: int = 8,
        ingest_shards: int = 1,
        gen_tracked: bool = False,
    ):
        self.capacity = int(capacity)
        obs_shape = (obs_dim,) if np.isscalar(obs_dim) else tuple(obs_dim)
        if obs_dtype is None:
            obs_dtype = np.float32 if len(obs_shape) == 1 else np.uint8
        self.block_rows = int(block_rows if block_rows is not None
                              else min(4096, self.capacity))
        self._device = device
        with obs_trace.span("replay.allocate", rows=self.capacity):
            self._store = DeviceStore(self.capacity, obs_shape, act_dim,
                                      obs_dtype, device=device,
                                      block_rows=self.block_rows)
            # what the buffer keeps on the device is committed to the
            # store's device, like the ring (device_ring.py): trees that
            # came back committed from their first commit would compile it
            # a second time
            self.trees = self._own(dper.init(self.capacity)) \
                if prioritized else None
        # The CPU backend's device_put hands back arrays that ALIAS aligned
        # host memory (``may_alias=False`` does not stop it), and the host
        # staging ring rewrites a frame's rows before an asynchronous commit
        # has read them: a torn block, one test run in six. There (tests,
        # development) a staged frame is copied first; a TPU always copies.
        self._host_aliased = next(iter(
            self._store.home.device_set)).platform == "cpu"
        self.prioritized = bool(prioritized)
        self.alpha = float(alpha)
        self.size = 0
        self.head = 0
        # Generation-tracked mode (the device-dealt sample plane,
        # replay/device_sampler.DeviceSampleDealer): ``add`` pre-assigns
        # and returns slot indices (the dealer drains every staged row to
        # the device inside the same buffer-lock window, so assignment
        # order IS commit order), a host int64 generation mirror fences
        # priority write-backs, and the fused commit additionally bumps a
        # device int32 generation array so the deal dispatch can snapshot
        # sampled generations without a host sync. Tree VALUES stay
        # host-computed (``p_ins = max_priority ** alpha`` in float64,
        # cast float32): float32 ``**`` is not bitwise portable between
        # numpy and XLA, and keeping the pow on the host is what makes
        # the device trees bitwise-equal to the float32 host twin oracle.
        self.gen_tracked = bool(gen_tracked)
        if self.gen_tracked:
            if not self.prioritized:
                raise ValueError("gen_tracked needs prioritized=True "
                                 "(it exists for the PER dealt plane)")
            if int(ingest_shards) > 1:
                raise ValueError(
                    "gen_tracked needs ingest_shards=1: direct-staged "
                    "shard rows bypass add(), which owns slot assignment")
            import jax.numpy as jnp

            self.max_priority = 1.0
            self.generation = np.zeros(self.capacity, np.int64)
            self.gen = self._own(jnp.zeros(self.capacity, jnp.int32))
            self._next_slot = 0
        obs_dtype = np.dtype(obs_dtype)
        # staging covers ~one ring (small buffers) capped at
        # ``staging_blocks`` blocks (big ones): deeper backlogs would only
        # be overwritten by later drains
        n_blocks = max(2, min(int(staging_blocks),
                              -(-self.capacity // self.block_rows)))
        specs = [(obs_shape, obs_dtype), ((act_dim,), np.float32),
                 ((), np.float32), (obs_shape, obs_dtype), ((), np.float32),
                 ((), np.float32)]
        self.ingest_shards = max(1, int(ingest_shards))
        if self.ingest_shards > 1:
            # sharded ingest plane: K workers stage concurrently into
            # private rings; the merge hands the SAME fixed-shape frame
            # stream to stage_block/commit_staged (staging.MultiRingStaging)
            from d4pg_tpu.replay.staging import MultiRingStaging

            self._staging = MultiRingStaging(specs, self.block_rows,
                                             n_blocks, self.ingest_shards)
        else:
            self._staging = HostStagingRing(specs, self.block_rows, n_blocks)
        # the frame on its way to the device: (frame, rows, block id,
        # position of its last row, time.monotonic() at stage_block). The
        # id and the position are what a trace follows a block by, from
        # ``fused.stage_block`` to ``fused.commit_staged``.
        self._inflight: tuple[TransitionBatch, int, int, int, float] | None \
            = None
        self.blocks_staged = 0
        # the highest position whose commit has been dispatched: what the
        # next chunk can sample (``learner.dispatch`` says it)
        self.landed = 0
        self._commit_fn = make_commit(
            self.capacity, self.block_rows, self.alpha, self._store.formats,
            prioritized=self.prioritized, gen_tracked=self.gen_tracked)
        self._commit = ring_program(self._commit_fn, self._store.formats)
        self._commit_tabled = False

    def _own(self, tree):
        import jax

        return jax.device_put(tree, self._store.home)

    @property
    def home(self):
        """The one-device sharding every device array of the buffer is
        committed to."""
        return self._store.home

    @property
    def formats(self) -> TransitionBatch:
        """The ``Format`` each ring field is pinned to (``None``: the
        compiler's layout); ``device_ring.py`` "Layout"."""
        return self._store.formats

    # -- ingest side (any thread, under the service's buffer lock) ---------
    def add(self, batch: TransitionBatch):
        """Stage host rows into the preallocated column-major staging ring;
        cheap (slice copies — no device work, no jit dispatch). Staging is
        bounded: if the learner pauses (long eval, checkpoint) while actors
        keep streaming, the oldest staged rows are dropped — they would
        only be overwritten by the next drain anyway, and an unbounded
        backlog could otherwise OOM the host.

        In ``gen_tracked`` mode ``add`` also PRE-ASSIGNS the rows' ring
        slots (returned as the insert indices the dealer mirrors) and
        bumps their host generations. Assignment order is commit order
        because the device dealer drains the staging ring inside the
        same buffer-lock window as this call — enforced by refusing the
        silent oldest-drop that would desynchronize slots from rows."""
        n = batch.obs.shape[0]
        if n == 0:
            return np.empty(0, np.int64) if self.gen_tracked else None
        if self.gen_tracked:
            if len(self._staging) + n > self._staging.size:
                raise RuntimeError(
                    "gen_tracked staging overflow: the dealer must drain "
                    "every add within its buffer-lock window (backlog "
                    f"{len(self._staging)} + {n} > {self._staging.size})")
            slots = (self._next_slot + np.arange(n)) % self.capacity
            self._next_slot = int((self._next_slot + n) % self.capacity)
            self.generation[slots] += 1
            self._staging.push(batch)
            return slots
        if self.ingest_shards > 1:
            self._staging.push(batch, shard=0)
        else:
            self._push(batch)
        return None

    def _push(self, batch: TransitionBatch) -> None:
        """Into the one staging ring; traced frames whose rows it dropped to
        admit these end their journey here."""
        if self._staging.push(batch):
            _tracer.shed_dropped(self._staging.consumed)

    def staged_position(self) -> tuple[int, int]:
        """``(through, dropped)``: the position of the newest row pushed
        into host staging (through the multi-ring: where the merged stream
        will stand once it holds every row pushed so far) and the rows
        staging has dropped so far. Under the service's buffer lock, like
        ``add``."""
        return self._staging.written, self._staging.dropped

    def add_sharded(self, batch: TransitionBatch, shard: int,
                    ticket: int | None = None) -> None:
        """Stage host rows into shard ``shard``'s private ring — the
        concurrent half of the sharded ingest plane. Safe WITHOUT the
        service buffer lock: each ring has a single pushing worker and
        its own leaf lock against the learner's merge (the shard worker
        call site in ``ReplayService._worker``). ``ticket`` orders the
        merge; per-shard tickets must ascend (the admission seq does)."""
        if batch.obs.shape[0] == 0:
            return
        if self.ingest_shards > 1:
            self._staging.push(batch, shard=shard, ticket=ticket)
        else:
            self._push(batch)

    def __len__(self) -> int:
        # staged + in-flight rows count toward warmup gates — they WILL be
        # trained on (drained before the next chunk)
        inflight = self._inflight[1] if self._inflight is not None else 0
        return min(self.size + len(self._staging) + inflight, self.capacity)

    # -- learner side (single owner of the device handles) -----------------
    @property
    def storage(self) -> TransitionBatch:
        return self._store.arrays

    # Every shipped caller of the three mutating learner-side entry
    # points below reaches them through ReplayService.ingest_stage/
    # ingest_commit/drain_device/load_replay_state, i.e. UNDER the
    # service's buffer lock; the guarded-by annotations declare that
    # caller contract to the unguarded-shared-write lock-graph rule
    # (tests drive the buffer directly, single-threaded).
    def stage_block(self) -> int:  # jaxlint: guarded-by=_buffer_lock
        """Start the H2D transfer of ONE pending block frame (a single
        ``jax.device_put`` of the fixed-shape [block_rows] views) — the
        only explicit transfer the ingest plane makes. No-op while a frame
        is already in flight (the double-buffer depth is one: block t+1
        stages while chunk t computes). Returns rows staged."""
        if self._inflight is not None:
            return 0
        views, n = self._staging.frame()
        if n == 0:
            return 0
        import jax

        # how long the frame's oldest row sat in host staging: measured
        # here, where the row waits, once per block
        now = time.monotonic()
        wait_ms = 1e3 * (now - (self._staging.oldest_push() or now))
        block = self.blocks_staged
        first = self._staging.consumed + 1
        through = first + n - 1
        # through the multi-ring a row's place in the merged stream is
        # fixed only now: the block says the tickets it carries
        tickets = getattr(self._staging, "tickets_through", None)
        stats = tickets(through) if tickets is not None else {}
        with obs_trace.span("fused.stage_block", block=block, rows=n,
                            wait_ms=wait_ms, first=first, through=through,
                            **stats):
            with obs_trace.span("fused.h2d"):
                if self._host_aliased:
                    views = TransitionBatch(*[np.array(v) for v in views])
                frame = (jax.device_put(views, self._device)
                         if self._device is not None
                         else jax.device_put(views))
            self._staging.pop(n)
            self._inflight = (frame, n, block, through, now)
            self.blocks_staged = block + 1
        _tracer.mark_through("h2d", through)
        return n

    def commit_staged(self) -> int:  # jaxlint: guarded-by=_buffer_lock
        """Land the in-flight frame: ONE jitted dispatch fusing the
        two-slice ring write with the PER tree insert (storage and trees
        donated). Learner thread only. Returns rows committed."""
        if self._inflight is None:
            return 0
        frame, n, block, through, staged_at = self._inflight
        self._inflight = None
        inflight_ms = 1e3 * (time.monotonic() - staged_at)
        start = np.int32(self.head)
        if self.gen_tracked:
            # host-f64 pow, f32 cast: the trees only see host-rounded
            # values (bitwise twin contract — see __init__)
            p_ins = np.float32(self.max_priority ** self.alpha)
            args = (self._store.pinned(), self.trees, self.gen, frame, start,
                    np.int32(n), p_ins, np.float32(self.max_priority))
        elif self.trees is not None:
            args = (self._store.pinned(), self.trees, frame, start,
                    np.int32(n))
        else:
            args = (self._store.pinned(), frame, start, np.int32(n))
        first = obs_trace.NULL_SPAN
        if not self._commit_tabled:  # first dispatch: enter the table
            from d4pg_tpu.io.profiling import abstract_args

            obs_trace.register_program("ingest.commit", self._commit_fn,
                             abstract_args(args))
            self._commit_tabled = True
            first = obs_trace.span("learner.first_dispatch",
                                   program="ingest.commit")
        with first, obs_trace.span("fused.commit_staged", block=block,
                                   rows=n, inflight_ms=inflight_ms,
                                   through=through):
            out = self._commit(*args)
        self.landed = through
        _tracer.mark_through("land", through)
        if self.gen_tracked:
            storage, self.trees, self.gen = out
        elif self.trees is not None:
            storage, self.trees = out
        else:
            storage = out
        self._store.swap_arrays(storage)
        self.head = int((self.head + n) % self.capacity)
        self.size = int(min(self.size + n, self.capacity))
        return n

    # priority write-back for the dealt plane: reached from the device
    # dealer's settle inside the commit thread's buffer-lock window
    def apply_priorities(self, idx, p_alpha) -> None:  # jaxlint: guarded-by=_buffer_lock
        """Scatter settled write-back priorities (already ``** alpha``,
        float32) into the device trees: ONE jitted dispatch, trees
        donated (commit thread is the single owner). ``idx`` rows equal
        to the TREE capacity are pads and are dropped — the dealer pads
        to fixed buckets so steady state never recompiles."""
        self.trees = dper.set_leaves_jitted(self.trees, idx, p_alpha)

    def drain(self) -> int:
        """Flush ALL staged rows to the device (stage + commit per block
        until the staging ring is empty). Learner thread only; used at
        cycle boundaries and before checkpoint snapshots. The overlapped
        per-chunk schedule calls ``stage_block``/``commit_staged``
        directly (learner/pipeline.IngestOverlap)."""
        total = self.commit_staged()
        while self.stage_block():
            total += self.commit_staged()
        return total

    def drain_per_row(self) -> int:
        """The pre-block reference drain: one scatter dispatch + one tree
        insert PER ROW. Kept as the bitwise-equivalence oracle for the
        block path (the block drain must land exactly these bytes and
        priorities). Not used by any shipped loop."""
        total = self.commit_staged()  # a device-staged frame goes block-wise
        while True:
            frame, n = self._staging.frame()
            if n == 0:
                break
            self._staging.pop(n)
            self.landed = self._staging.consumed
            for i in range(int(n)):
                idx = np.array([self.head], np.int32)
                row = TransitionBatch(*[np.asarray(v)[i:i + 1]
                                        for v in frame])
                # this IS the per-row anti-pattern (one H2D-carrying
                # dispatch per transition), preserved as baseline/oracle
                self._store.write(idx, row)
                if self.trees is not None:
                    self.trees = dper.insert_jitted(self.trees, idx,
                                                    self.alpha)
                self.head = int((self.head + 1) % self.capacity)
                self.size = int(min(self.size + 1, self.capacity))
            total += int(n)
        return total

    def state_dict(self) -> dict:
        """Ring + tree state as host numpy for checkpointing. Learner
        thread only (drains staged rows first so nothing is lost)."""
        import jax

        from d4pg_tpu.replay.uniform import pack_rows

        self.drain()
        rows = jax.device_get(
            TransitionBatch(*[arr[:self.size] for arr in self.storage]))
        d = pack_rows(rows, self.head, self.size, self.capacity)
        if self.trees is not None:
            cap = self.trees.capacity
            d["leaf_priorities"] = np.asarray(
                self.trees.sum_tree[cap:cap + self.size])
            # gen-tracked: the HOST scalar is authoritative (write-back
            # settles raise it between commits; the device copy only
            # refreshes at the next commit dispatch)
            d["max_priority"] = (float(self.max_priority)
                                 if self.gen_tracked
                                 else float(self.trees.max_priority))
        return d

    def snapshot(self) -> dict:
        """Crash-recovery cut: ``state_dict`` (the drain inside it
        collapses every staging ring head into the device ring, so the
        cut has NO in-flight rows) plus the staging plane's ticket floor
        when sharded — everything a fresh buffer needs to resume bitwise
        at this point. Learner thread only, like ``state_dict``."""
        d = self.state_dict()
        stg = getattr(self._staging, "snapshot", None)
        if stg is not None:
            d["staging"] = stg()
        return d

    def restore(self, d: dict) -> None:
        """Load a ``snapshot`` cut into this (fresh) buffer. Same caller
        contract as ``load_state_dict``: reached under the service's
        buffer lock (or single-threaded, e.g. the bench oracle)."""
        self.load_state_dict(d)
        stg = getattr(self._staging, "restore", None)
        if stg is not None and "staging" in d:
            stg(d["staging"])

    # restore mutates ring+tree state: reached via ReplayService.
    # load_replay_state under the buffer lock, like the paths above
    def load_state_dict(self, d: dict) -> None:  # jaxlint: guarded-by=_buffer_lock
        import jax.numpy as jnp

        from d4pg_tpu.replay.uniform import unpack_rows

        batch, head, size = unpack_rows(d, self.capacity)
        if batch is not None:
            self._store.write(np.arange(size, dtype=np.int32), batch)
        self.size = size
        self.head = head
        if self.trees is not None:
            trees = dper.init(self.capacity)
            if size:
                trees = dper.set_leaves_jitted(
                    trees, jnp.arange(size),
                    jnp.asarray(d["leaf_priorities"], jnp.float32))
            self.trees = self._own(trees._replace(
                max_priority=jnp.float32(d.get("max_priority", 1.0))))
        if self.gen_tracked:
            # restore opens a fresh generation epoch: live rows at 1,
            # everything else 0, host mirror and device copy in lockstep
            # — any block dealt against the pre-restore state carries
            # generations that no longer match and is fenced at settle
            self.max_priority = float(d.get("max_priority", 1.0))
            self._next_slot = self.head
            self.generation = np.zeros(self.capacity, np.int64)
            self.generation[:self.size] = 1
            self.gen = self._own(jnp.asarray(self.generation, jnp.int32))
