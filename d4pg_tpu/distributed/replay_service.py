"""Replay service: the learner-side ingest point for actor transitions.

Replaces the reference's per-process private replay buffers (each hogwild
worker kept its own, ``ddpg.py:78-89``) with ONE central service the actors
stream into — the D4PG-paper architecture. Ingest is bounded queues drained
by background workers, so actor `add` calls never block the learner's
sample path; heartbeats give the failure detection the reference lacks
(SURVEY.md §5: "a dead worker just ends").

Sharded ingest plane (``num_ingest_shards=K``; docs/architecture.md
"Sharded receiver"): admission, decode and staging are partitioned across
K shards so the receiver host can spend K cores on the frame path instead
of one. Ownership model:

  - an **ingest shard** owns: its bounded admission deque, its shed
    watermark and shed/decode counters, and one worker thread. Everything
    a shard owns is guarded by that shard's single condition variable —
    counter and queue mutate under the SAME lock, so a shard snapshot is
    always consistent. Frame decode (``transport.decode_frame``) and the
    fused path's column-major staging run on the shard worker.
  - the **commit thread** (the single writer of replay state) merges the
    shard outputs back into ONE coherent buffer: every admitted batch
    carries a global admission ticket ``seq``; the commit thread inserts
    strictly in ``seq`` order (shed or undecodable tickets are tombstoned
    so the merge never stalls on them), folds the observation normalizer
    in that same order (single-writer invariant preserved), and takes the
    buffer lock once per merged group. At K=1 this degenerates to exactly
    the old single-drain behavior: one queue, arrival order, same
    counters.
  - the **learner thread** stays the single owner of device handles
    (``stage_block``/``commit_staged``), exactly as before.

Lock order: every lock here is a ``core.locking`` tiered object from the
ONE declared hierarchy (service > buffer > commit > shard > ring;
monotone tier descent per thread). A shard condition is a LEAF lock —
neither the buffer lock, the service lock nor the merge condition may be
acquired while holding one. The commit thread acquires ``_buffer_lock``
and ``_lock`` sequentially, never nested inside a shard condition. The
discipline is enforced three ways: syntactically by the ``lock-order``
jaxlint rule, interprocedurally by the ``lock-cycle`` lock-graph pass
(``python -m d4pg_tpu.lint --locks``), and at runtime by the tier
assertions the fleet chaos smoke runs with (``core/locking.py``).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Optional

import numpy as np

from d4pg_tpu.core.locking import TieredCondition, TieredLock
from d4pg_tpu.distributed.transport import decode_frame, raw_frame_meta_ex
from d4pg_tpu.obs import trace as obs_trace
from d4pg_tpu.obs.containment import contained_crash
from d4pg_tpu.obs.flight import EVENT_ADMISSION_REJECT, record_event
from d4pg_tpu.obs.registry import REGISTRY
from d4pg_tpu.obs.trace import RECORDER as _tracer
from d4pg_tpu.replay.prioritized import PrioritizedReplayBuffer
from d4pg_tpu.replay.uniform import ReplayBuffer, TransitionBatch

# Seconds the ordered merge may make zero progress while shard output is
# waiting before it skips ahead to the smallest ready ticket (counted in
# ``order_breaks``). A lost ticket is a bug, but the fleet-plane rule is
# degrade-and-count, never wedge.
_ORDER_GRACE_S = 5.0


class _IngestShard:
    """One ingest shard: admission deque + counters, all owned by ``cond``.

    The worker thread and producers synchronize ONLY through ``cond``:
    producers wait on it for space (blocking mode) and the worker notifies
    after popping; counters mutate under the same lock as the queue they
    describe, so ``snapshot()`` is consistent by construction."""

    __slots__ = ("idx", "capacity", "shed_at", "cond", "q", "sheds",
                 "shed_rows", "decode_errors", "rows_in", "staged_rows",
                 "admit_fails", "sheds_by_class")

    def __init__(self, idx: int, capacity: int, shed_at: int | None):
        self.idx = idx
        self.capacity = capacity
        self.shed_at = shed_at
        self.cond = TieredCondition("shard")
        # class-attributed shed ledger (elastic admission): class name
        # -> rows shed; written under ``cond`` with the queue it
        # describes, like every other shard counter
        self.sheds_by_class: dict = {}
        # items: (seq, data, codec, actor_id, rows, count, trace); codec
        # None means ``data`` is an already-decoded TransitionBatch, else
        # it is the undecoded wire payload for ``decode_frame(data,
        # codec)``. ``trace`` is the sampled frame's trace id (or None)
        # riding the item so every later stage can stamp its span.
        self.q: deque = deque()
        self.sheds = 0
        self.shed_rows = 0
        self.decode_errors = 0
        self.rows_in = 0
        self.staged_rows = 0
        self.admit_fails = 0  # rejected admissions (full past timeout)

    def snapshot(self) -> dict:
        with self.cond:
            return {
                "shard": self.idx,
                "queue_depth": len(self.q),
                "sheds": self.sheds,
                "shed_rows": self.shed_rows,
                "decode_errors": self.decode_errors,
                "rows_in": self.rows_in,
                "staged_rows": self.staged_rows,
                "admit_fails": self.admit_fails,
                "capacity": self.capacity,
                "shed_at": self.shed_at,
                "sheds_by_class": dict(self.sheds_by_class),
            }


def _merge_class_counts(dicts) -> dict:
    """Sum per-shard ``sheds_by_class`` ledgers into one fleet view."""
    out: dict = {}
    for d in dicts:
        for k, v in d.items():
            out[k] = out.get(k, 0) + v
    return out


class ReplayService:
    def __init__(
        self,
        buffer: ReplayBuffer,
        ingest_capacity: int = 256,
        heartbeat_timeout: float = 30.0,
        obs_norm=None,
        shed_watermark: float | None = None,
        num_ingest_shards: int = 1,
        generation: int = 0,
        admission=None,
    ):
        """``shed_watermark`` (fraction of ``ingest_capacity``, fleet-plane
        degradation): when an ingest shard's deque stands at or above the
        watermark, ``add`` sheds the OLDEST queued batch to admit the
        newest instead of blocking the caller — a stalled drain degrades
        the replay distribution (newest-biased, counted in ``sheds``/
        ``shed_rows``) rather than wedging 256 receiver threads. None
        (default) keeps the block-or-False contract of the training
        loop. ``ingest_capacity`` and the watermark are PER SHARD, so
        K=1 semantics are bit-compatible with the old single queue."""
        self.buffer = buffer
        # Optional RunningMeanStd (envs/normalizer.py). The COMMIT thread
        # is the SINGLE writer: it folds every ingested row (local,
        # spawned or remote actors alike — they all stream RAW
        # observations) into the statistics in admission-ticket order and
        # inserts the rows normalized, so the learner only ever samples
        # standardized data. Actors receive read-only statistics for
        # their policy input via the weight channel.
        self.obs_norm = obs_norm
        self.num_ingest_shards = max(1, int(num_ingest_shards))
        buf_shards = getattr(buffer, "ingest_shards", 1)
        if buf_shards not in (1, self.num_ingest_shards):
            # a mismatched sharded buffer would hand one staging ring two
            # pushing workers with interleaved tickets, breaking the
            # per-ring ticket-ascending assumption of the merge commit
            raise ValueError(
                f"buffer.ingest_shards={buf_shards} must be 1 or match "
                f"num_ingest_shards={self.num_ingest_shards}")
        self._env_steps = 0
        # Rows landed in replay state, counted ONCE at commit time for
        # both the buffer-insert and direct-stage paths (the registry's
        # no-double-count ledger; see _insert_group).
        self._rows_committed = 0
        # Crash-recovery plane (all under self._lock): the service
        # generation id. Raw frames stamped with an OLDER generation are
        # fenced at admission — they were encoded against a pre-crash
        # service and may duplicate rows already inside the restored
        # snapshot (transport.py "Generation extension"). restore() bumps
        # past the snapshot's generation; a supervisor restarting WITHOUT
        # a snapshot passes ``generation`` explicitly.
        self._generation = int(generation)
        self._fenced_frames = 0
        self._fenced_rows = 0
        self._lock = TieredLock("service")
        # Guards ALL buffer mutation/reads: the commit thread's insert
        # races the learner thread's sample()/update_priorities()
        # otherwise (segment-tree aggregates are multi-word updates).
        self._buffer_lock = TieredLock("buffer")
        # Sample-on-ingest dealer (replay/sampler.SampleDealer), attached
        # via attach_dealer. Written under _buffer_lock; the replica-side
        # readers (queue_writeback) take a benign set-once atomic read —
        # forcing them through the buffer lock would reintroduce the very
        # contention the dealer removes.
        self._dealer = None
        # Batches accepted into a shard but not yet committed; counted on
        # the producer side so flush() can't slip through the window
        # between queue-pop and buffer insert.
        self._pending = 0
        self._heartbeats: dict[str, float] = {}
        self._owner: dict[str, int] = {}  # actor -> owning ingest shard
        self._heartbeat_timeout = heartbeat_timeout
        # Fleet-plane degradation + recovery state (all under self._lock):
        # evicted actors are remembered so a resumed heartbeat RE-ADMITS
        # them (and records the outage length) instead of counting them
        # dead forever; shed counters surface every dropped batch.
        shed_at = (
            None if shed_watermark is None
            else max(1, min(ingest_capacity,
                            int(shed_watermark * ingest_capacity))))
        self._shed_at = shed_at
        # watermark FRACTION retained so set_ingest_depth (the elastic
        # autoscaler's actuator) can recompute shed_at when it resizes
        # the shard deques live
        self._shed_watermark = shed_watermark
        # Optional elastic.AdmissionPolicy: priority-tagged shedding.
        # None (default) keeps the flat shed-oldest behavior bit-for-bit;
        # with a policy the shed victim is the oldest batch of the WORST
        # queued class, and every shed/reject is class-attributed in
        # sheds_by_class. Frozen/stateless, so sharing it across shard
        # conditions adds no lock edge.
        self._admission = admission
        self.evictions = 0
        self.readmissions = 0
        self._evicted: dict[str, float] = {}
        self._recovery_s: list[float] = []
        self._shards = [
            _IngestShard(i, int(ingest_capacity), shed_at)
            for i in range(self.num_ingest_shards)
        ]
        # The fused direct-stage fast path: shard workers copy rows
        # straight into the buffer's per-shard staging ring (thread-safe
        # by ring ownership — see replay/staging.MultiRingStaging) and
        # the commit thread only does the ordered accounting. Requires a
        # shard-aware buffer and no normalizer (the fold must stay
        # ticket-ordered on the single writer).
        self._direct_stage = (
            self.num_ingest_shards > 1 and obs_norm is None
            and getattr(buffer, "ingest_shards", 1) > 1
            and hasattr(buffer, "add_sharded"))
        # Ordered merge state, all under _commit_cond: per-shard output
        # deques (seq-ascending by construction), tombstoned tickets, and
        # the next ticket to commit.
        self._commit_cond = TieredCondition("commit")
        self._out: list[deque] = [deque() for _ in self._shards]
        self._skip: set[int] = set()
        self._next_seq = 0
        self._seq = itertools.count()
        # a buffer that stages on the host says where its newest row stands
        # and how many rows staging has dropped (fused_buffer.py); the
        # second as the commit thread last saw it
        self._staged_position = getattr(buffer, "staged_position", None)
        self._staging_dropped = (self._staged_position()[1]
                                 if self._staged_position else 0)
        self.order_breaks = 0
        self._stop = threading.Event()
        self._workers = [
            threading.Thread(target=self._worker, args=(s,), daemon=True,
                             name=f"ingest-shard-{s.idx}")
            for s in self._shards
        ]
        self._commit_thread = threading.Thread(
            target=self._commit_loop, daemon=True, name="ingest-commit")
        # compat alias: the fleet harness's deadlock verdict checks the
        # drain/commit thread's liveness under this name
        self._drain_thread = self._commit_thread
        for t in self._workers:
            t.start()
        self._commit_thread.start()
        # Unified-registry membership (d4pg_tpu/obs/registry): the
        # service's consistent snapshot IS the provider — held weakly,
        # last-registered service wins the slot, dropped on close().
        REGISTRY.register_provider("ingest", self.ingest_stats)

    # -- actor-facing ------------------------------------------------------
    def add(self, batch: TransitionBatch, actor_id: str = "local",
            block: bool = True, timeout: float | None = 5.0,
            count_env_steps: bool = True, shard: int | None = None) -> bool:
        """Enqueue transitions (backpressure via the bounded shard deque).
        Returns False if the deque stayed full past ``timeout``.

        ``count_env_steps=False`` for rows that do not correspond to fresh
        environment interaction (HER relabels) — otherwise the env_steps
        counter inflates by (1 + her_ratio)x in HER runs.

        With a ``shed_watermark`` configured, ``add`` NEVER blocks: a
        shard at the watermark sheds its oldest batch (counted) to admit
        this one, and the call returns True.

        ``shard`` pins the ingest shard (the sharded receiver passes the
        connection's shard); by default actors hash onto a stable one."""
        n = int(batch.obs.shape[0])
        s = self._route(actor_id, shard)
        self.heartbeat(actor_id, shard=s.idx)
        if n == 0:
            return True
        return self._admit(s, batch, None, actor_id, n, count_env_steps,
                           block, timeout)

    def add_payload(self, payload: bytes, shard: int = 0,
                    codec: str = "npz") -> bool:
        """Admit one UNDECODED wire frame from the sharded receiver
        (``transport.TransitionReceiver(on_payload=...)``). Raw (v2)
        frames are admitted on header metadata alone — actor id and row
        count come from ``raw_frame_meta`` — and decoded later on the
        owning shard's worker; npz frames carry no cheap header, so they
        are decoded here (the connection thread, exactly where the
        unsharded receiver decodes them).

        Backpressure matches the unsharded receiver's: with a shed
        watermark configured (fleet plane) admission never blocks — a
        full shard sheds oldest, counted; WITHOUT one (train.py default)
        a full shard blocks this connection thread up to 5 s, and a
        frame rejected past the timeout is counted in the shard's
        ``admit_fails`` rather than vanishing. A learner stall therefore
        backs pressure up into the sender exactly as at K=1."""
        trace = None
        gen = None
        if codec == "raw":
            try:
                # header-only: trace id/birth ride the v2 extension, so a
                # sampled frame is traceable (and shed-accountable with a
                # terminal span) before any column byte is parsed
                actor_id, n, count, trace, gen = raw_frame_meta_ex(payload)
            except Exception:
                s = self._shards[shard % self.num_ingest_shards]
                with s.cond:
                    s.decode_errors += 1
                record_event("decode_error", shard=s.idx, where="admission")
                return False
            data: object = payload
        else:
            try:
                actor_id, batch, count = decode_frame(payload, codec)
            except Exception:
                s = self._shards[shard % self.num_ingest_shards]
                with s.cond:
                    s.decode_errors += 1
                record_event("decode_error", shard=s.idx, where="admission")
                return False
            n, codec, data = int(batch.obs.shape[0]), None, batch
        s = self._shards[shard % self.num_ingest_shards]
        self.heartbeat(actor_id, shard=s.idx)
        fenced = False
        if gen is not None:
            # generation fence (crash recovery): a frame stamped with a
            # PRE-restart generation was encoded before the crash and
            # retried verbatim — its rows may already sit inside the
            # restored snapshot (the sender's sendall could have landed
            # before the kill). Admitting it risks a duplicate; fencing
            # it is a DECLARED loss (fenced_rows), keeping recovery
            # exactly-once w.r.t. committed rows.
            with self._lock:
                if gen < self._generation:
                    self._fenced_frames += 1
                    self._fenced_rows += n
                    fenced = True
        if fenced:
            REGISTRY.counter("ingest.rows_fenced").inc(n)
            record_event("generation_fenced", shard=s.idx, actor=actor_id,
                         rows=n, frame_gen=gen)
            if trace is not None:
                # the traced frame ends HERE: a fence is a terminal
                # outcome (like a shed), never an orphan span
                _tracer.begin(trace[0], trace[1])
                _tracer.terminal_shed(trace[0])
            return True
        if n == 0:
            return True
        return self._admit(s, data, codec, actor_id, n, count,
                           block=s.shed_at is None, timeout=5.0,
                           trace=trace)

    def _route(self, actor_id: str, shard: int | None) -> _IngestShard:
        if shard is not None:
            return self._shards[shard % self.num_ingest_shards]
        if self.num_ingest_shards == 1:
            return self._shards[0]
        return self._shards[hash(actor_id) % self.num_ingest_shards]

    def _admit(self, s: _IngestShard, data, codec, actor_id: str, rows: int,
               count: bool, block: bool, timeout: float | None,
               trace: tuple[int, float] | None = None) -> bool:
        with self._lock:
            self._pending += 1
        shed_seqs: list[int] = []
        shed_tids: list[int] = []
        shed_batches = 0
        admitted = False
        rejected_cls: str | None = None
        pol = self._admission
        # ingest.admit: the producer's side of the handoff, one span an
        # add; ``wait_ms`` only when it blocked for a deque slot, ``seq``
        # (the admission ticket a trace follows the add by) only when
        # admitted
        with obs_trace.span("ingest.admit", rows=rows) as admit_span, s.cond:
            if s.shed_at is not None:
                # shed admission: bounded work, never blocks. The counter
                # and the deque mutate under the same lock — the
                # consistent-snapshot contract of ingest_stats(). Without
                # a policy this is flat shed-oldest; with one the victim
                # is the oldest batch of the WORST queued class, and an
                # incoming batch that ranks below everything queued is
                # itself rejected (class-attributed) rather than evicting
                # more-protected work.
                inc_cls = (None if pol is None
                           else pol.classify_actor(actor_id))
                admitted = True
                while len(s.q) >= s.shed_at:
                    if pol is None:
                        victim = 0
                    else:
                        classes = [pol.classify_actor(it[3]) for it in s.q]
                        victim = pol.shed_victim(classes, inc_cls)
                        if victim is None:
                            admitted = False
                            rejected_cls = pol.class_name(inc_cls)
                            s.sheds_by_class[rejected_cls] = (
                                s.sheds_by_class.get(rejected_cls, 0) + rows)
                            break
                    old = s.q[victim]
                    del s.q[victim]
                    s.sheds += 1
                    s.shed_rows += old[4]
                    if pol is not None:
                        name = pol.class_name(classes[victim])
                        s.sheds_by_class[name] = (
                            s.sheds_by_class.get(name, 0) + old[4])
                    shed_seqs.append(old[0])
                    if old[6] is not None:
                        shed_tids.append(old[6][0])
                    shed_batches += 1
            elif len(s.q) >= s.capacity:
                if block:
                    began = time.monotonic()
                    deadline = (None if timeout is None
                                else began + timeout)
                    while (len(s.q) >= s.capacity
                           and not self._stop.is_set()):
                        remaining = (None if deadline is None
                                     else deadline - time.monotonic())
                        if remaining is not None and remaining <= 0:
                            break
                        s.cond.wait(0.1 if remaining is None
                                    else min(remaining, 0.1))
                    admit_span.set_metadata(
                        wait_ms=1e3 * (time.monotonic() - began))
                admitted = len(s.q) < s.capacity
            else:
                admitted = True
            if admitted:
                seq = next(self._seq)
                s.q.append((seq, data, codec, actor_id, rows, count, trace))
                admit_span.set_metadata(seq=seq)
                s.rows_in += rows
                s.cond.notify_all()
            else:
                s.admit_fails += 1
        # observability, all OUTSIDE the shard condition (obs locks are
        # terminal, but tiered hold times stay honest): admission span +
        # flight breadcrumb, terminal spans for everything shed here.
        if admitted:
            if trace is not None:
                _tracer.begin(trace[0], trace[1])
                _tracer.record_span(trace[0], "admission")
            record_event("admit", shard=s.idx, actor=actor_id, rows=rows)
            REGISTRY.counter("ingest.rows_admitted").inc(rows)
        else:
            if rejected_cls is not None:
                # class-policy rejection: a load verdict attributed to the
                # incoming batch's priority class, distinct from the
                # timeout path's admit_fail
                record_event(EVENT_ADMISSION_REJECT, plane="ingest",
                             shard=s.idx, actor=actor_id, cls=rejected_cls,
                             rows=rows)
            record_event("admit_fail", shard=s.idx, actor=actor_id,
                         rows=rows)
            if trace is not None:
                _tracer.begin(trace[0], trace[1])
                _tracer.terminal_shed(trace[0])
        if shed_seqs:
            self._tombstone(shed_seqs)
            if self._dealer is not None:
                self._dealer.mark_dead_seqs(shed_seqs)
            record_event("shed", shard=s.idx, batches=shed_batches,
                         seqs=shed_seqs[:8])
            for tid in shed_tids:
                _tracer.terminal_shed(tid)
        dropped = shed_batches + (0 if admitted else 1)
        if dropped:
            with self._lock:
                self._pending -= dropped  # sheds never reach the commit
        return admitted

    def _tombstone(self, seqs: list[int]) -> None:
        with self._commit_cond:
            self._skip.update(seqs)
            self._commit_cond.notify_all()

    def heartbeat(self, actor_id: str, shard: int | None = None) -> None:
        now = time.monotonic()
        with self._lock:
            evicted_at = self._evicted.pop(actor_id, None)
            if evicted_at is not None:
                # the actor came back: re-admit and record the outage
                self.readmissions += 1
                if len(self._recovery_s) < 10_000:
                    self._recovery_s.append(now - evicted_at)
            self._heartbeats[actor_id] = now
            if shard is not None:
                self._owner[actor_id] = shard
        if evicted_at is not None:
            record_event("readmission", actor=actor_id,
                         outage_s=round(now - evicted_at, 3))

    # -- learner-facing ----------------------------------------------------
    def sample(self, batch_size: int, beta: float = 0.4,
               weight_base: float | None = None):
        """PER: (batch, weights, idx, generation); uniform: batch. Mirrors
        the learner's buffer-kind dispatch (``ddpg.py:187-197``); the
        generation snapshot guards the priority write-back against the
        commit thread overwriting a sampled slot in flight."""
        with self._buffer_lock:
            if isinstance(self.buffer, PrioritizedReplayBuffer):
                batch, w, idx = self.buffer.sample(
                    batch_size, beta=beta, weight_base=weight_base)
                return batch, w, idx, self.buffer.generation[idx].copy()
            return self.buffer.sample(batch_size)

    def sample_chunk(self, k: int, batch_size: int, beta: float = 0.4,
                     weight_base: float | None = None):
        """K stacked batches in one storage gather: (batches [K, B, ...],
        weights-or-None, idx [K, B], generation-or-None [K, B]) — the
        K-updates-per-dispatch sample path (``learner/pipeline.py``). The
        generation snapshot lets the deferred priority write-back skip
        slots the commit thread overwrote in flight."""
        with self._buffer_lock:
            if isinstance(self.buffer, PrioritizedReplayBuffer):
                batches, w, idx = self.buffer.sample_chunk(
                    k, batch_size, beta=beta, weight_base=weight_base)
                return batches, w, idx, self.buffer.generation[idx].copy()
            batches, _, idx = self.buffer.sample_chunk(k, batch_size)
            return batches, None, idx, None

    def weight_base(self) -> float | None:
        """The local shard's IS-weight base ``z`` (see
        ``PrioritizedReplayBuffer.weight_base``); None for uniform replay."""
        with self._buffer_lock:
            if isinstance(self.buffer, PrioritizedReplayBuffer):
                return self.buffer.weight_base()
            return None

    def update_priorities(
        self,
        idx: np.ndarray,
        priorities: np.ndarray,
        generation: np.ndarray | None = None,
    ) -> None:
        if isinstance(self.buffer, PrioritizedReplayBuffer):
            with self._buffer_lock:
                self.buffer.update_priorities(idx, priorities,
                                              generation=generation)

    def attach_dealer(self, dealer) -> None:
        """Wire a ``replay/sampler.SampleDealer`` into the commit path.
        From here on every ordered commit mirrors its inserts into the
        dealer's slice trees and deals ready-to-train blocks into the
        per-replica rings; replicas feed TD priorities back through
        :meth:`queue_writeback` (sampler tier only — the replica sample
        path never acquires the buffer lock again)."""
        with self._buffer_lock:
            dealer.resync(self.buffer)
            self._dealer = dealer
        # Demand-driven top-up: a replica pop that frees ring room wakes
        # the commit loop (its idle tick deals the refill) instead of
        # leaving the refill to the next ingest commit or the ~10 Hz
        # timeout — a consumer faster than the commit cadence would
        # otherwise starve on an empty ring. The kick runs on the
        # replica thread with no locks held (the ring condition is
        # released before the callback fires), so taking the commit
        # condition here is a top-level acquire, not an ascent.
        for ring in dealer.rings:
            ring.on_room = self._kick_commit

    def _kick_commit(self) -> None:
        with self._commit_cond:
            self._commit_cond.notify_all()

    def queue_writeback(self, idx: np.ndarray, priorities: np.ndarray,
                        generation: np.ndarray) -> None:
        """Replica-side priority write-back on the dealt path. Enqueues
        under the ``sampler`` tier; the owning ingest shard's worker (and
        the commit thread's settle-before-draw) applies it to the slice
        trees. Generation-fenced exactly like ``update_priorities``."""
        dealer = self._dealer
        if dealer is None:
            raise RuntimeError("queue_writeback requires an attached "
                               "SampleDealer (attach_dealer)")
        dealer.queue_writeback(idx, priorities, generation)

    def drain_device(self) -> int:
        """Flush ALL rows staged by a fused-path buffer
        (``replay/fused_buffer.py``) onto the device. Called by the
        LEARNER thread at cycle/chunk boundaries — it is the single owner
        of the device handles, so the ingest workers only stage host rows
        and never dispatch device work."""
        drain = getattr(self.buffer, "drain", None)
        if drain is None:
            return 0
        with self._buffer_lock:
            return drain()

    def ingest_commit(self) -> int:
        """Land the in-flight staged block (one jitted ring-write + tree
        insert dispatch; no explicit H2D). Learner thread, called right
        BEFORE a fused-chunk dispatch so the chunk samples the freshest
        rows. No-op (0) for buffers without the block-drain API."""
        commit = getattr(self.buffer, "commit_staged", None)
        if commit is None:
            return 0
        return self._under_buffer_lock(commit)

    def _under_buffer_lock(self, call):
        """``call()`` under the buffer lock, the learner's wait for the
        lock (the commit thread holds it while it stages a group) as a
        span of its own."""
        with obs_trace.span("ingest.lock_wait"):
            self._buffer_lock.acquire()
        try:
            return call()
        finally:
            self._buffer_lock.release()

    def ingest_stage(self) -> int:
        """Start the H2D transfer of the next staged block (ONE
        ``jax.device_put``). Learner thread, called right AFTER a fused
        chunk is dispatched so the transfer overlaps the chunk's compute
        — the ≤ 1 explicit-H2D-per-chunk schedule
        (``learner/pipeline.IngestOverlap``). Falls back to a full
        synchronous drain for buffers without the block API (sharded
        fused replay), preserving the old per-chunk semantics there."""
        stage = getattr(self.buffer, "stage_block", None)
        if stage is None:
            return self.drain_device()
        return self._under_buffer_lock(stage)

    def replay_state(self) -> dict:
        """Buffer contents + priorities for checkpointing (learner
        thread; SURVEY.md §5 elastic recovery)."""
        with self._buffer_lock:
            return self.buffer.state_dict()

    def load_replay_state(self, d: dict) -> None:
        with self._buffer_lock:
            self.buffer.load_state_dict(d)

    def snapshot(self, quiesce_timeout: float = 10.0) -> dict:
        """Consistent snapshot of the SERVING state at a quiesced cut:
        buffer columns + PER tree (``state_dict`` — the fused buffer
        drains its staging rings first, so ring heads collapse into the
        cut), the admission-ticket/commit floor, the row ledger and the
        service generation. The cut is quiesced by ``flush`` (every
        admitted batch committed), then captured lock-by-lock in the
        ``ingest_stats`` pattern — strictly SEQUENTIAL acquisitions, so
        the tier hierarchy gains no new edges. Restoring this dict into
        a fresh service (``restore``) resumes at exactly this cut;
        persisted next to the orbax learner checkpoint by
        ``io/checkpoint.py`` so learner and replay restore together."""
        self.flush(timeout=quiesce_timeout)
        with self._buffer_lock:
            buf = self.buffer.state_dict()
        with self._commit_cond:
            next_seq = self._next_seq
        with self._lock:
            return {
                "schema": 1,
                "buffer": buf,
                "next_seq": next_seq,
                "env_steps": self._env_steps,
                "rows_committed": self._rows_committed,
                "generation": self._generation,
            }

    def restore(self, snap: dict) -> None:
        """Load a ``snapshot`` cut into this (fresh or quiesced) service:
        buffer + PER tree, ticket floor (the admission counter resumes
        ABOVE every committed ticket, so merge order stays monotone
        across the restart) and the row ledger. The service generation
        is bumped PAST the snapshot's — every raw frame encoded against
        the pre-crash service now fences at admission."""
        if not isinstance(snap, dict) or "buffer" not in snap:
            raise ValueError("not a replay service snapshot (no buffer cut)")
        with self._buffer_lock:
            self.buffer.load_state_dict(snap["buffer"])
        floor = int(snap.get("next_seq", 0))
        with self._commit_cond:
            self._next_seq = floor
            self._seq = itertools.count(floor)
            self._skip.clear()
            for dq in self._out:
                dq.clear()
            self._commit_cond.notify_all()
        with self._lock:
            self._env_steps = int(snap.get("env_steps", 0))
            self._rows_committed = int(snap.get("rows_committed", 0))
            self._generation = max(self._generation,
                                   int(snap.get("generation", 0)) + 1)
        dealer = self._dealer
        if dealer is not None:
            # drop blocks dealt against the pre-restore state, then
            # rebuild the slice trees from the restored buffer; pending
            # write-backs die with the resync (their generations are
            # fenced by the bump above anyway)
            dealer.clear_rings()
            with self._buffer_lock:
                dealer.resync(self.buffer)

    @property
    def generation(self) -> int:
        """Current service generation (the id the receiver's greeting
        hands to connecting senders — transport.TransitionReceiver)."""
        with self._lock:
            return self._generation

    @property
    def env_steps(self) -> int:
        with self._lock:
            return self._env_steps

    def set_env_steps(self, n: int) -> None:
        """Seed the env-step counter (checkpoint resume)."""
        with self._lock:
            self._env_steps = int(n)

    def set_ingest_depth(self, capacity: int) -> None:
        """Live-resize the per-shard admission deques (elastic actuator).

        The shed watermark (when configured) is recomputed at the SAME
        fraction of the new capacity, so a deepened shard genuinely
        absorbs a flash crowd instead of shedding at the old bound.
        Each shard condition is taken and released in turn at top level
        (shard tier, nothing else held) — no new lock edges, and a
        snapshot taken mid-resize just reports the conservative
        (minimum) bound via ``ingest_stats()``."""
        cap = max(1, int(capacity))
        for s in self._shards:
            with s.cond:
                s.capacity = cap
                if s.shed_at is not None and self._shed_watermark is not None:
                    s.shed_at = max(
                        1, min(cap, int(self._shed_watermark * cap)))
                s.cond.notify_all()  # blocked adds may now fit

    def __len__(self) -> int:
        with self._buffer_lock:
            return len(self.buffer)

    def wait_until(self, min_size: int, timeout: float = 300.0) -> bool:
        """Block until the buffer holds ``min_size`` transitions (warmup
        gate, ``main.py:200-207``)."""
        deadline = time.monotonic() + timeout
        while len(self.buffer) < min_size:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.01)
        return True

    def dead_actors(self) -> list[str]:
        """Actors currently considered dead: heartbeat-stale ones plus the
        evicted-and-not-yet-returned set. An evicted actor that resumes
        heartbeating (or streaming — ``add`` heartbeats) is RE-ADMITTED by
        ``heartbeat`` and drops out of this list; before that fix an
        eviction was permanent and a restarted actor with the same id
        stayed counted dead forever."""
        now = time.monotonic()
        with self._lock:
            stale = [
                a for a, t in self._heartbeats.items()
                if now - t > self._heartbeat_timeout
            ]
            return stale + [a for a in self._evicted if a not in stale]

    def evict_dead(self) -> list[str]:
        """Move heartbeat-stale actors into the evicted set (their next
        heartbeat re-admits them and records the outage as a recovery
        sample). Returns the newly evicted ids. Called periodically by the
        fleet monitor; idempotent between actor state changes."""
        now = time.monotonic()
        with self._lock:
            stale = [
                a for a, t in self._heartbeats.items()
                if now - t > self._heartbeat_timeout
            ]
            for a in stale:
                del self._heartbeats[a]
                self._evicted[a] = now
                self.evictions += 1
        for a in stale:
            record_event("eviction", actor=a)
        return stale

    def evicted_actors(self) -> list[str]:
        with self._lock:
            return list(self._evicted)

    def ingest_stats(self) -> dict:
        """Degradation/recovery counters for the fleet plane. Snapshot
        consistency: every counter is read under the SAME lock that
        writes it — per-shard counters atomically with the queue they
        describe (one shard condition each), the env_steps/pending pair
        and heartbeat state atomically under the service lock — so the
        numbers can never show e.g. a shed whose queue pop is missing.
        Cross-shard totals are sums of per-shard-consistent snapshots."""
        per_shard = [s.snapshot() for s in self._shards]
        with self._commit_cond:
            commit_backlog = sum(len(dq) for dq in self._out)
            order_breaks = self.order_breaks
        with self._lock:
            merged = {
                "env_steps": self._env_steps,
                "rows_committed": self._rows_committed,
                "pending": self._pending,
                "evictions": self.evictions,
                "readmissions": self.readmissions,
                "recovery_s": list(self._recovery_s),
                "live_actors": len(self._heartbeats),
                "evicted": len(self._evicted),
                "generation": self._generation,
                "fenced_frames": self._fenced_frames,
                "fenced_rows": self._fenced_rows,
            }
        # rows the fused path's host staging discarded under a backlog
        # deeper than itself (process-wide: one service a process)
        merged["rows_dropped"] = REGISTRY.counter("fused.rows_dropped").value
        merged.update({
            "queue_depth": sum(p["queue_depth"] for p in per_shard),
            "sheds": sum(p["sheds"] for p in per_shard),
            "shed_rows": sum(p["shed_rows"] for p in per_shard),
            "decode_errors": sum(p["decode_errors"] for p in per_shard),
            "admit_fails": sum(p["admit_fails"] for p in per_shard),
            # class-attributed shed ledger (elastic admission): covers
            # both evicted-queued rows and policy-rejected incoming rows,
            # so it can exceed shed_rows when incoming work is bounced
            "sheds_by_class": _merge_class_counts(
                p["sheds_by_class"] for p in per_shard),
            # live per-shard deque bound — the elastic autoscaler's
            # set_ingest_depth actuator target (min across shards so a
            # mid-resize snapshot reports the conservative bound)
            "ingest_capacity": min(p["capacity"] for p in per_shard),
            "num_ingest_shards": self.num_ingest_shards,
            "commit_backlog": commit_backlog,
            "order_breaks": order_breaks,
            "per_shard": per_shard,
        })
        return merged

    # -- internals ---------------------------------------------------------
    # Max batches folded into one merged commit pass: bounds the lock
    # hold (the learner's sample path waits on the same lock) while still
    # amortizing it ~64x under a streaming fleet.
    _COALESCE = 64

    def _worker(self, s: _IngestShard) -> None:
        """Shard worker: pop a coalesced group, decode wire payloads
        (the CPU-heavy half of ingest), optionally direct-stage into the
        buffer's shard ring, and hand the group to the ordered merge.

        Backpressure discipline: at most ONE decoded group per shard sits
        in the merge's inbox — the worker waits for the commit thread to
        take its previous group before popping the next. Decode of group
        t+1 thereby overlaps the insert of group t (the pipeline), while
        a slow commit still backs pressure up into the shard deque where
        the shed watermark / blocking-add contract lives, exactly like
        the single drain thread it replaces."""
        try:
            self._worker_loop(s)
        except Exception as e:
            contained_crash("ingest.shard_worker", e)

    def _worker_loop(self, s: _IngestShard) -> None:
        while not self._stop.is_set():
            dealer = self._dealer
            if dealer is not None:
                # the owning shard drains ITS slices' priority write-back
                # queues — top-level sampler-tier acquire, no other lock
                # held, so the slice trees keep a single writer per slice
                dealer.drain_writebacks_for_shard(s.idx)
            with self._commit_cond:
                while self._out[s.idx] and not self._stop.is_set():
                    self._commit_cond.wait(timeout=0.1)
            with s.cond:
                if not s.q:
                    s.cond.wait(timeout=0.1)
                items = []
                while s.q and len(items) < self._COALESCE:
                    items.append(s.q.popleft())
                if items:
                    s.cond.notify_all()  # space freed: wake blocked adds
            if not items:
                continue
            out, dead, dead_tids, staged = [], [], [], 0
            for seq, data, codec, actor_id, rows, count, trace in items:
                tid = trace[0] if trace is not None else None
                if codec is not None:
                    try:
                        actor_id, batch, count = decode_frame(data, codec)
                    except Exception:
                        dead.append(seq)
                        if tid is not None:
                            dead_tids.append(tid)
                        continue
                    rows = int(batch.obs.shape[0])
                    if tid is not None:
                        _tracer.record_span(tid, "decode")
                else:
                    batch = data
                if self._direct_stage:
                    # rows land in the buffer's per-shard staging ring
                    # HERE, on the shard core; the commit thread only
                    # settles the ordered accounting for this ticket (the
                    # push is this path's host staging, so the span that
                    # says so is opened here, by ticket)
                    with obs_trace.span("ingest.host_stage", batches=1,
                                        rows=rows, seq_lo=seq, seq_hi=seq):
                        self.buffer.add_sharded(batch, s.idx, ticket=seq)
                    staged += rows
                    batch = None
                if tid is not None:
                    # 'stage': rows copied into the shard's staging ring
                    # (direct path) or handed to the ordered-merge inbox
                    _tracer.record_span(tid, "stage")
                out.append((seq, actor_id, batch, rows, count, tid))
            if dead or staged:
                with s.cond:
                    s.decode_errors += len(dead)
                    s.staged_rows += staged
            with self._commit_cond:
                self._out[s.idx].extend(out)
                if dead:
                    self._skip.update(dead)
                self._commit_cond.notify_all()
            if dead:
                record_event("decode_error", shard=s.idx, tickets=dead[:8],
                             n=len(dead))
                if dealer is not None:
                    dealer.mark_dead_seqs(dead)
                for tid in dead_tids:
                    _tracer.terminal_shed(tid)  # tombstoned, not leaked
                with self._lock:
                    self._pending -= len(dead)

    def _pop_ready(self, group: list, shed_tids: list | None = None,
                   shed_seqs: list | None = None) -> int:
        """Pop the next run of in-ticket-order items (caller holds
        ``_commit_cond``). Tombstoned tickets are consumed and skipped.

        Returns the number of STALE tickets discarded: a ticket the
        order-break valve advanced past (its worker held the popped group
        too long) later lands at the head of its shard's deque with
        ``seq < _next_seq`` — forever unpoppable by the equality match
        below, which would gate that shard's worker on a never-emptying
        inbox and wedge the shard permanently. Degrade-and-count instead:
        drop it, count it in ``order_breaks``; the caller settles its
        ``_pending`` accounting — and the discards' terminal trace spans
        (collected into ``shed_tids``) — outside this condition."""
        stale = 0
        while len(group) < self._COALESCE:
            while self._next_seq in self._skip:
                self._skip.discard(self._next_seq)
                self._next_seq += 1
            found = None
            for dq in self._out:
                while dq and dq[0][0] < self._next_seq:
                    item = dq.popleft()
                    self.order_breaks += 1
                    stale += 1
                    if shed_tids is not None and item[5] is not None:
                        shed_tids.append(item[5])
                    if shed_seqs is not None:
                        shed_seqs.append(item[0])
                if dq and dq[0][0] == self._next_seq:
                    found = dq.popleft()
                    break
            if found is None:
                break
            group.append(found)
            self._next_seq += 1
        return stale

    def _commit_loop(self) -> None:
        """The single writer of replay state: ordered K-way merge of the
        shard outputs, normalizer fold, one buffer-lock acquisition per
        merged group."""
        try:
            self._commit_run()
        except Exception as e:
            contained_crash("ingest.commit", e)

    def _commit_run(self) -> None:
        last_progress = time.monotonic()
        while True:
            group: list = []
            shed_tids: list = []
            stale_seqs: list = []
            with self._commit_cond:
                stale = self._pop_ready(group, shed_tids, stale_seqs)
                if not group:
                    if self._stop.is_set():
                        return
                    self._commit_cond.wait(timeout=0.1)
                    stale += self._pop_ready(group, shed_tids, stale_seqs)
                if group or stale:
                    # inbox slots freed: wake gated shard workers
                    self._commit_cond.notify_all()
                backlog = any(self._out[i] for i in range(len(self._out)))
            if group:
                # merge-pop spans, recorded after the condition released
                # (the pop order inside one group is ticket order; one
                # timestamp per group is the honest granularity — the
                # commit thread popped them in one critical section)
                for item in group:
                    if item[5] is not None:
                        _tracer.record_span(item[5], "merge")
            if stale:
                # discarded tickets never reach _insert_group; settle the
                # flush() accounting here (never inside _commit_cond —
                # lock order: _lock is not taken under the merge cond)
                record_event("order_break", kind_detail="stale_discard",
                             n=stale)
                for tid in shed_tids:
                    _tracer.terminal_shed(tid)
                if self._dealer is not None:
                    self._dealer.mark_dead_seqs(stale_seqs)
                with self._lock:
                    self._pending -= stale
            if group:
                last_progress = time.monotonic()
                self._insert_group(group)
            elif (backlog and time.monotonic() - last_progress
                    > _ORDER_GRACE_S):
                # safety valve: a ticket vanished without a tombstone.
                # Skip to the smallest ready ticket (counted) rather than
                # wedging the whole ingest plane behind it.
                advanced = False
                with self._commit_cond:
                    heads = [dq[0][0] for dq in self._out if dq]
                    if heads and min(heads) > self._next_seq:
                        self.order_breaks += 1
                        advanced = True
                        self._next_seq = min(heads)
                        # tombstones below the new floor can never be
                        # consumed by _pop_ready's equality walk; prune
                        # them or the set grows for the service lifetime
                        self._skip = {t for t in self._skip
                                      if t >= self._next_seq}
                if advanced:
                    record_event("order_break", kind_detail="floor_advance")
                last_progress = time.monotonic()
            if not group and self._dealer is not None:
                # idle deal tick: settle write-backs and top the rings
                # back up even when ingest is quiet — still the commit
                # thread, still one buffer-lock window per tick
                dealer = self._dealer
                with self._buffer_lock:
                    dealt = dealer.ingest_and_deal((), self.buffer)
                if dealt:
                    dealer.publish(dealt)

    def _insert_group(self, group: list) -> None:
        dealer = self._dealer
        dealt: list = []
        through = None  # where the group's last row stands in host staging
        try:
            if self.obs_norm is not None:
                # Only obs rows feed the estimator; next_obs is
                # normalized but never folded in. The episode-FINAL
                # next_obs is thereby excluded — intentional: there is
                # no row-level marker for "truly final" here (done=1
                # tags every n-step fold of a terminal AND HER success
                # relabels mid-trajectory, so done-gating would weight
                # terminal-adjacent states 2-5x instead), and the
                # omission is one state in T per episode. Stats fold
                # BEFORE any of the group's rows are normalized, in
                # admission-ticket order — same estimator as the
                # per-batch loop, regardless of shard interleaving.
                for j, (seq, aid, batch, rows, cnt, tid) in enumerate(group):
                    if batch is None:
                        continue
                    self.obs_norm.update(batch.obs)
                    group[j] = (seq, aid, batch._replace(
                        obs=self.obs_norm.normalize(batch.obs),
                        next_obs=self.obs_norm.normalize(batch.next_obs),
                    ), rows, cnt, tid)
            # ingest.host_stage: the commit thread's buffer-lock section,
            # which on the fused path pushes the group into host staging.
            # It says the tickets it holds (ticket order: first and last)
            # and, for a buffer that stages, the position of the group's
            # last row after the push and the rows dropped to admit it.
            with obs_trace.span("ingest.host_stage", batches=len(group),
                                rows=sum(item[3] for item in group),
                                seq_lo=group[0][0], seq_hi=group[-1][0]
                                ) as host_span, self._buffer_lock:
                if dealer is None:
                    for _seq, _aid, batch, _rows, _cnt, _tid in group:
                        if batch is not None:  # None: already direct-staged
                            self.buffer.add(batch)
                else:
                    # sample-on-ingest: insert, mirror, settle write-backs
                    # and draw dealt blocks inside the ONE buffer-lock
                    # window this commit already owned — the collapsed
                    # ingest->insert->sample->fetch pass
                    inserts = []
                    for _seq, _aid, batch, _rows, _cnt, _tid in group:
                        if batch is not None:
                            inserts.append(
                                (self.buffer.add(batch), _seq, _tid))
                    dealt = dealer.ingest_and_deal(inserts, self.buffer)
                if self._staged_position is not None:
                    through, dropped = self._staged_position()
                    host_span.set_metadata(
                        through=through,
                        dropped=dropped - self._staging_dropped)
                    self._staging_dropped = dropped
        finally:
            committed = 0
            with self._lock:
                for _seq, _aid, _batch, rows, count, _tid in group:
                    if count:
                        self._env_steps += rows
                    committed += rows
                self._rows_committed += committed
                self._pending -= len(group)
            # The rows ledger counts each row ONCE, here, where replay
            # state changed — NEVER at direct-stage time (staged_rows is
            # a per-shard marker of which path ran, a SUBSET of these
            # rows, not an addend; summing both double-counts the fast
            # path — the K=1↔K=2 counter-equivalence test pins this).
            REGISTRY.counter("ingest.rows_committed").inc(committed)
            _tracer.mark_committed(
                [tid for *_rest, tid in group if tid is not None],
                through=through)
        if dealt:
            # ring pushes + deal spans AFTER every service lock released
            dealer.publish(dealt)

    def flush(self, timeout: float = 5.0) -> None:
        """Block until every accepted batch has been committed."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if self._pending == 0:
                    return
            time.sleep(0.005)

    def close(self) -> None:
        self.flush()
        self.kill()

    def kill(self) -> None:
        """SIGKILL-equivalent teardown (the chaos supervisor's weapon):
        stop the ingest threads WITHOUT flushing. Accepted-but-uncommitted
        batches are discarded, exactly what process death does to them;
        rows committed after the last durable snapshot die with the
        instance too — recovery restores that snapshot into a FRESH
        service and fences the stale generation at admission. Safe to
        call twice (provider unregistration is instance-guarded, thread
        joins are idempotent)."""
        REGISTRY.unregister_provider("ingest", self.ingest_stats)
        if self._dealer is not None:
            # closes the dealt rings too, waking any blocked replica pop
            self._dealer.close()
        self._stop.set()
        for s in self._shards:
            with s.cond:
                s.cond.notify_all()
        with self._commit_cond:
            self._commit_cond.notify_all()
        for t in self._workers:
            t.join(timeout=2.0)
        self._commit_thread.join(timeout=2.0)
