"""Async host->device batch staging.

SURVEY.md §7 "hard parts": replay sampling + H2D transfer must hide under
the XLA learner step. ``DeviceStager`` keeps one batch in flight: while the
TPU executes step t on batch t, the host samples and ``device_put``s batch
t+1 (JAX dispatch is async, so ``device_put`` returns immediately and the
transfer overlaps with compute).

``MultiRingStaging`` is the host half of the SHARDED ingest plane: K
private column-major staging rings (one per ingest shard, so K workers
copy rows concurrently without sharing a cache line of bookkeeping) whose
contents merge back into ONE fixed-shape frame stream for the existing
single-``device_put`` + single-jitted-commit fused dispatch.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from typing import Callable, Iterator

import jax

from d4pg_tpu.core.locking import TieredCondition, TieredLock


class MultiRingStaging:
    """K per-shard host staging rings + a ticket-ordered merge frame.

    Interface-compatible with ``HostStagingRing`` on the consumer side
    (``frame()``/``pop()``/``take()``/``__len__``), so the fused buffer's
    ``stage_block``/``commit_staged``/``drain_per_row`` run unchanged on
    the merged stream — the ≤1-device_put-per-block invariant and the
    per-row bitwise oracle survive sharding untouched.

    Ownership: shard ``i``'s worker is the only pusher of ring ``i``;
    each ring (and its record deque) is guarded by one leaf lock
    (``core.locking.TieredLock`` at the bottom ``ring`` tier), held only
    for the slice-copy — never while taking any service or buffer lock.
    The direction is enforced by the ``lock-order``/``lock-cycle``
    jaxlint rules statically and by the tier assertions at runtime.

    Merge-commit ordering rule: every pushed batch carries a monotonic
    admission ticket (per-ring ascending; globally unique). ``frame()``
    refills an internal merge ring by repeatedly draining the record
    with the SMALLEST ticket among the shard ring heads, so rows land on
    the device in admission order whenever the plane is quiescent (the
    bitwise K=1↔K=2 equivalence bar); rows still being decoded on a
    straggler shard can be overtaken mid-flight — the merge never
    blocks the learner's stage call on a slow shard.

    Positions (``HostStagingRing``) are those of the MERGED stream, fixed
    only at ``_refill``: ``consumed`` is the merge ring's, ``written`` is
    where the merged stream will stand once it holds every row pushed so
    far (an upper bound on the position of any of them, exact when the
    shards are quiescent), and ``tickets_through`` says which tickets a
    block carries, which is what ties a row to its block here.
    """

    def __init__(self, specs, block_rows: int, n_blocks: int,
                 shards: int):
        from d4pg_tpu.replay.fused_buffer import HostStagingRing

        self.shards = max(1, int(shards))
        self.block_rows = int(block_rows)
        self._rings = [HostStagingRing(specs, block_rows, n_blocks)
                       for _ in range(self.shards)]
        self._ring_locks = [TieredLock("ring") for _ in range(self.shards)]
        # per-ring (ticket, rows, the caller's ticket or None) records,
        # ticket-ascending; the third says what a block's span may report: a
        # ticket of this object's own making orders the merge and means
        # nothing to a reader
        self._records: list[deque] = [deque() for _ in range(self.shards)]
        self._merge = HostStagingRing(specs, block_rows, 2)
        self._ticket = itertools.count()
        # (merged position of a caller's ticket's last row, the ticket),
        # until a block has carried it; learner thread only
        self._merged: deque = deque()

    def __len__(self) -> int:
        n = len(self._merge)
        for i in range(self.shards):
            with self._ring_locks[i]:
                n += len(self._rings[i])
        return n

    @property
    def written(self) -> int:
        return self._merge.written + len(self) - len(self._merge)

    @property
    def consumed(self) -> int:
        return self._merge.consumed

    @property
    def dropped(self) -> int:
        return sum(ring.dropped for ring in self._rings)

    # -- producer side (one worker per shard) ------------------------------
    def push(self, batch, shard: int = 0, ticket: int | None = None) -> None:
        i = shard % self.shards
        ring, records = self._rings[i], self._records[i]
        with self._ring_locks[i]:
            t = next(self._ticket) if ticket is None else ticket
            n = min(int(batch.obs.shape[0]), ring.size)
            overflow = max(0, len(ring) + n - ring.size)
            ring.push(batch)
            # the ring dropped its oldest rows to admit these: trim the
            # same rows off the oldest records so tickets stay aligned
            # with ring contents
            while overflow and records:
                t0, n0, seq0 = records[0]
                if n0 <= overflow:
                    records.popleft()
                    overflow -= n0
                else:
                    records[0] = (t0, n0 - overflow, seq0)
                    overflow = 0
            records.append((t, n, ticket))

    # -- consumer side (learner thread) ------------------------------------
    def _refill(self) -> None:
        """Move rows into the merge ring, smallest head ticket first,
        until it holds a full block or the shard rings run dry."""
        while len(self._merge) < self.block_rows:
            best = None
            for i in range(self.shards):
                with self._ring_locks[i]:
                    if self._records[i]:
                        t = self._records[i][0][0]
                        if best is None or t < best[0]:
                            best = (t, i)
            if best is None:
                return
            _t, i = best
            with self._ring_locks[i]:
                if not self._records[i] or self._records[i][0][0] != _t:
                    continue  # a push overflowed the head away; re-scan
                _t, n, seq = self._records[i].popleft()
                room = self._merge.size - len(self._merge)
                if n > room:
                    # only part of the record fits this pass: keep the
                    # remainder (same ticket) at the head for the next
                    self._records[i].appendleft((_t, n - room, seq))
                    n, seq = room, None
                # the rows keep the time they were first staged, so the
                # merged frame's wait is its oldest shard row's
                at = self._rings[i].oldest_push()
                for piece in self._rings[i].take(n):
                    self._merge.push(piece, at)
            if seq is not None:  # the caller's ticket's last row has its place
                self._merged.append((self._merge.written, seq))

    def tickets_through(self, through: int) -> dict:
        """``seq_lo`` / ``seq_hi`` of the tickets whose last row lies in
        the merged stream up to ``through`` and in no earlier block: what
        the block that ends there says of itself (nothing for rows pushed
        under tickets of this object's own)."""
        seqs = []
        while self._merged and self._merged[0][0] <= through:
            seqs.append(self._merged.popleft()[1])
        return {"seq_lo": min(seqs), "seq_hi": max(seqs)} if seqs else {}

    # -- crash-recovery cut -------------------------------------------------
    def snapshot(self) -> dict:
        """Ticket floor + residual depth at a (drained) cut. The fused
        buffer's ``state_dict`` drains every ring before calling this,
        so ``staged_rows`` is 0 on a consistent snapshot — recorded
        anyway so a non-quiesced cut is self-describing. Consuming one
        ticket to learn the floor is benign: tickets only need to ascend
        per ring, gaps never block the merge."""
        floor = next(self._ticket)
        return {"ticket_floor": int(floor), "staged_rows": len(self)}

    def restore(self, d: dict) -> None:
        """Reseat the ticket counter ABOVE the snapshot's floor so every
        post-restore push stays merge-ordered after every pre-crash
        ticket. Ring contents are NOT restored — a consistent cut has
        none (see ``snapshot``); rows in flight at the crash are the
        declared fence/shed losses of the recovery plane."""
        self._ticket = itertools.count(int(d.get("ticket_floor", 0)) + 1)

    def frame(self):
        self._refill()
        return self._merge.frame()

    def oldest_push(self) -> float | None:
        """When the oldest row of the merged stream was first staged in
        its shard ring (after ``frame()`` has refilled the merge)."""
        return self._merge.oldest_push()

    def pop(self, n: int) -> None:
        self._merge.pop(n)

    def take(self, n: int):
        self._refill()
        return self._merge.take(n)


class DeviceStager:
    """Double-buffered prefetch of host batches onto a device (or sharding).

    With ``with_aux=True`` the sample_fn returns ``(payload, aux)``: the
    payload is ``device_put`` (async), the aux rides along untouched on the
    host — e.g. PER sample indices that must come back to the host for the
    priority write-back (``ddpg.py:252-255``).
    """

    def __init__(
        self,
        sample_fn: Callable[[], object],
        device=None,
        with_aux: bool = False,
        put_fn: Callable | None = None,
    ):
        self._sample = sample_fn
        self._device = device
        self._with_aux = with_aux
        # Custom staging (e.g. multi-host: a host-local device_put cannot
        # address other hosts' devices, so the multi-host runtime stages
        # via jax.make_array_from_process_local_data instead —
        # parallel/multihost.make_global_chunk).
        self._put_fn = put_fn
        self._inflight = None

    def _put(self):
        sampled = self._sample()
        batch, aux = sampled if self._with_aux else (sampled, None)
        if self._put_fn is not None:
            staged = self._put_fn(batch)
        elif self._device is not None:
            staged = jax.device_put(batch, self._device)
        else:
            staged = jax.device_put(batch)
        return (staged, aux) if self._with_aux else staged

    def next(self, prefetch: bool = True):
        """Return the prefetched batch and (unless ``prefetch=False``) start
        staging the following one. Pass ``prefetch=False`` on the last batch
        a consumer will take before an ``invalidate()`` — otherwise that
        trailing sample is staged only to be thrown away."""
        out = self._inflight if self._inflight is not None else self._put()
        self._inflight = self._put() if prefetch else None
        return out

    def invalidate(self) -> None:
        """Drop the in-flight batch (e.g. after a buffer mutation that makes
        the prefetched sample undesirable). The next ``next()`` samples
        fresh."""
        self._inflight = None

    def __iter__(self) -> Iterator:
        while True:
            yield self.next()


class DealtBlockRing:
    """Bounded ring of ready-to-train dealt blocks, one per learner
    replica (the sample-on-ingest plane, ``replay/sampler.py``).

    Ownership: single producer — the commit thread's dealer — and a
    single consumer — the owning replica. The dealer reserves room under
    its own ``sampler``-tier critical section (``room()``) and pushes
    AFTER releasing it; since only consumers shrink the queue between
    the reservation and the push, a reserved push can only fail if the
    ring was closed. All queue state lives under one bottom-tier
    ``ring`` condition, so the replica's blocking ``pop`` holds nothing
    above the leaf tier — the replica sample path never touches the
    buffer lock.
    """

    def __init__(self, capacity: int = 4):
        self.capacity = max(1, int(capacity))
        self._cond = TieredCondition("ring")
        self._q: deque = deque()
        self._closed = False
        # Demand kick, set by ReplayService.attach_dealer: called after a
        # pop frees room — with the ring condition RELEASED, so the
        # callback may take the commit condition at top level (a ring ->
        # commit ascent under the leaf lock would be the merge-wedge
        # shape) — to wake the commit loop for an immediate top-up tick.
        # Without it the ring refills only on ingest commits and the
        # ~10 Hz idle tick, and a consumer faster than the commit cadence
        # starves on an almost-always-empty ring.
        self.on_room: Callable[[], None] | None = None

    @property
    def closed(self) -> bool:
        with self._cond:
            return self._closed

    def room(self) -> int:
        with self._cond:
            return 0 if self._closed else max(0, self.capacity - len(self._q))

    def depth(self) -> int:
        with self._cond:
            return len(self._q)

    def offer(self, block) -> bool:
        """Producer push (named uniquely on purpose: ``push`` would
        name-collide with ``HostStagingRing.push`` in the lint lock
        graph's call resolution, manufacturing a ring->ring edge)."""
        with self._cond:
            if self._closed or len(self._q) >= self.capacity:
                return False
            self._q.append(block)
            self._cond.notify_all()
            return True

    def pop(self, timeout: float | None = None):
        """Next dealt block, blocking up to ``timeout`` seconds (forever
        when None); None on timeout or close."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while not self._q:
                if self._closed:
                    return None
                if deadline is None:
                    self._cond.wait()
                else:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return None
                    self._cond.wait(remaining)
            block = self._q.popleft()
            self._cond.notify_all()
        kick = self.on_room
        if kick is not None:
            kick()
        return block

    def clear(self) -> int:
        """Drop all queued blocks (replica respawn: a fresh consumer must
        not train on blocks dealt to its dead predecessor mid-kill).
        Returns the number dropped."""
        with self._cond:
            n = len(self._q)
            self._q.clear()
            self._cond.notify_all()
        kick = self.on_room
        if n and kick is not None:
            kick()
        return n

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()


class DeviceDealtBlockRing(DealtBlockRing):
    """``DealtBlockRing`` for DEVICE-resident dealt blocks
    (``replay/device_sampler.DeviceSampleDealer``): queue mechanics are
    identical, but ``clear`` — the replica-kill / restore path — also
    explicitly ``delete()``s each dropped block's device buffers. A
    host block's rows are reclaimed by the GC the moment the ring drops
    its reference; a device block's rows are HBM that would otherwise
    linger until the next GC cycle, so a kill burst could transiently
    hold ring_capacity * K * B rows of dead sample memory per replica.
    Deleting eagerly makes clear-on-kill reclaim immediate (pinned by
    the devsample chaos test).
    """

    def clear(self) -> int:
        with self._cond:
            dropped = list(self._q)
            self._q.clear()
            self._cond.notify_all()
        for block in dropped:
            for arr in (*block.batches, block.weights, block.idx,
                        block.gen):
                # host blocks are numpy (no delete); on a jax.Array,
                # delete() of an already-donated buffer is a no-op, so
                # nothing here needs catching
                delete = getattr(arr, "delete", None)
                if delete is not None:
                    delete()
        kick = self.on_room
        if dropped and kick is not None:
            kick()
        return len(dropped)
