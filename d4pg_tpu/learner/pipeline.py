"""Pipelined K-chunk learner loop — the shipped hot path.

One place implements the sample -> stage -> scanned-update -> priority
write-back pipeline, so what ``train.py`` ships and what the tests and
``benchmark/`` drive is the SAME loop (the reference scope per step is ``ddpg.py:200-255``: sample,
nets, projection, optimizer, priorities). Schedule per chunk t:

  1. take the staged chunk t (sampled/device_put while t-1 computed),
     and immediately stage chunk t+1 (host work, overlaps device),
  2. dispatch the K-step scanned update for chunk t (async),
  3. once more than ``depth`` chunks are in flight, write back the
     oldest chunk's PER priorities (its td_error D2H copy was started at
     dispatch time, so the flush rarely blocks).

PER priorities therefore land with staleness <= (depth + 1) * K grad
steps (Ape-X-style bounded lag); ``updates_per_dispatch=1`` in the config
restores exact per-step write-back semantics via the non-pipelined path
in ``train.py``. (The fused device path, ``learner/fused.py``, does not
need any of this — its write-back happens inside the dispatch.)
"""

from __future__ import annotations

import threading
import weakref
from contextlib import contextmanager
from typing import Any, Callable, Optional

import numpy as np

from d4pg_tpu.obs import trace as obs_trace
from d4pg_tpu.replay.staging import DeviceStager


class IngestDispatchError(RuntimeError):
    """A second consumer raced the service's single ingest-dispatch slot
    (two live ``IngestOverlap`` owners, or concurrent commit/stage/flush
    calls on one). The double-buffer schedule is single-consumer by
    construction — a silent second dispatcher would interleave ring
    writes and corrupt replay, so this fails loudly instead."""


class IngestOverlap:
    """Double-buffers actor→ring ingest against the in-flight fused chunk.

    The fused path's only host job is moving staged actor rows into the
    device ring between chunks (``replay/fused_buffer.py``). Done naively
    (a full synchronous drain before every dispatch) the H2D transfer
    serializes with the chunk; this schedule overlaps them:

        ingest.commit()        # block t's ring write+tree insert (async
                               # jitted dispatch, no transfer) — rows are
                               # samplable by the chunk dispatched next
        dispatch fused chunk t
        ingest.stage()         # ONE device_put of block t+1 — the H2D
                               # rides under chunk t's compute

    giving a hard bound of ≤ 1 explicit H2D per chunk in steady state
    (verified by ``TransferSentinel`` in tests/test_ingest.py). Backpressure is structural: at most
    ``block_rows`` rows land per chunk; a deeper backlog drains at cycle
    boundaries (``flush``), and the staging ring drops oldest beyond its
    bound. Works against ``ReplayService`` (whose ``ingest_stage`` falls
    back to a full drain for buffers without the block API).

    **Single-consumer, enforced.** The commit/stage handoff mutates the
    service's ONE staged-block slot; two dispatchers would interleave
    ring writes and silently corrupt replay. Construction therefore
    claims the service's ingest-dispatch slot (a weakly-held owner
    token — a dropped overlap releases it via GC, an explicit successor
    calls ``release()``), and every dispatch holds a non-blocking busy
    token so a concurrent commit/stage/flush — the shape a second
    learner replica would produce — raises ``IngestDispatchError``
    instead of corrupting. Multi-replica learners (``--learners N>1``)
    must use the host-sampled path, which is why ``LearnerReplica``
    only builds a ``FusedLoop`` when it is the sole consumer.
    """

    def __init__(self, service):
        owner_ref = getattr(service, "_ingest_overlap_owner", None)
        owner = owner_ref() if owner_ref is not None else None
        if owner is not None:
            raise IngestDispatchError(
                "ReplayService already has a live IngestOverlap consumer "
                f"({owner!r}); the fused ingest handoff is single-consumer "
                "— release() the current owner first, or use the "
                "host-sampled path for concurrent learner replicas")
        dealer = getattr(service, "_dealer", None)
        if dealer is not None and getattr(dealer, "owns_commit", False):
            # Device-dealt mode: the attached dealer drains the staged
            # slot itself inside every ingest's buffer-lock window (the
            # deal must see the block it just committed). A second
            # commit/stage driver would interleave with those drains and
            # corrupt the handoff, so refuse up front instead of racing.
            raise IngestDispatchError(
                "ReplayService has a device-dealt sampler attached "
                f"({type(dealer).__name__}); its commit thread owns the "
                "ingest dispatch — dealt replicas consume from their "
                "rings, no IngestOverlap")
        service._ingest_overlap_owner = weakref.ref(self)
        self._service = service
        # busy token, held across each dispatch into the service: plain
        # non-blocking Lock — contention IS the defect being detected,
        # so the loser raises instead of waiting
        self._busy = threading.Lock()
        self.rows_committed = 0
        self.rows_staged = 0

    @contextmanager
    def _dispatch(self, op: str):
        if not self._busy.acquire(blocking=False):
            raise IngestDispatchError(
                f"concurrent IngestOverlap.{op}() while another dispatch "
                "is in flight — the double-buffer handoff is "
                "single-consumer")
        try:
            owner_ref = getattr(self._service, "_ingest_overlap_owner", None)
            if owner_ref is None or owner_ref() is not self:
                raise IngestDispatchError(
                    f"IngestOverlap.{op}() after ownership moved to another "
                    "consumer (release()d, or a successor claimed the slot)")
            yield
        finally:
            self._busy.release()

    # ``ingest.commit`` / ``ingest.stage``: the host time of the two
    # handoff calls, each over ``ingest.lock_wait`` (the service's buffer
    # lock) and the buffer's own ``fused.commit_staged`` /
    # ``fused.stage_block``, which carry the block's id.
    def commit(self) -> int:
        with obs_trace.span("ingest.commit") as sp, self._dispatch("commit"):
            n = self._service.ingest_commit()
            self.rows_committed += n
            sp.set_metadata(rows=n)
            return n

    def stage(self) -> int:
        with obs_trace.span("ingest.stage") as sp, self._dispatch("stage"):
            n = self._service.ingest_stage()
            self.rows_staged += n
            sp.set_metadata(rows=n)
            return n

    def flush(self) -> int:
        """Synchronous full drain (cycle boundary / checkpoint): every
        staged row lands before the next sample."""
        with self._dispatch("flush"):
            n = self._service.drain_device()
            self.rows_committed += n
            return n

    def release(self) -> None:
        """Give up the service's ingest-dispatch slot (idempotent) so a
        successor consumer — e.g. a respawned replica — can claim it."""
        owner_ref = getattr(self._service, "_ingest_overlap_owner", None)
        if owner_ref is not None and owner_ref() is self:
            self._service._ingest_overlap_owner = None


class ChunkPipeline:
    """Drives ``multi_update`` over prefetched chunks.

    ``sample_fn() -> ((batches, weights), aux)``: host-side sample of one
    [K, B, ...] chunk; ``weights``/``aux`` are None for uniform replay
    (``update_fn(state, batches, None)``).
    ``write_back(aux, td)``: PER priority update, td shaped [K, B].
    ``sharding``: optional NamedSharding for the staged chunk (mesh path).
    """

    def __init__(
        self,
        update_fn: Callable,
        sample_fn: Callable[[], tuple],
        write_back: Optional[Callable[[Any, np.ndarray], None]] = None,
        sharding=None,
        fetch_td: Optional[Callable] = None,
        put_fn: Optional[Callable] = None,
        depth: int = 2,
    ):
        self._update = update_fn
        self._write_back = write_back
        # How to pull td_error to the host. Default: full fetch. Multi-host
        # passes a local-shard extractor (a host can only read its own rows
        # of the globally-sharded [K, B] td_error).
        self._fetch_td = fetch_td or (lambda m: np.asarray(m["td_error"]))
        # put_fn: custom staging (multi-host global-array assembly);
        # default is device_put onto ``sharding``.
        self._stager = DeviceStager(sample_fn, device=sharding,
                                    with_aux=True, put_fn=put_fn)
        # In-flight dispatch depth: the PER write-back for chunk t blocks
        # on t's td_error, i.e. on t's whole dispatch — a blocking
        # device->host sync per chunk. Keeping up to `depth`
        # chunks in flight amortizes it; priority staleness grows to
        # <= (depth + 1) * K steps (Ape-X-style bounded lag).
        self._depth = max(1, int(depth))

    def invalidate(self) -> None:
        """Drop the staged chunk (sync-mode cycle boundary: train only on
        post-collect samples)."""
        self._stager.invalidate()

    def run(
        self,
        state,
        n_chunks: int,
        on_chunk: Optional[Callable] = None,
        final_prefetch: bool = True,
    ):
        """Run ``n_chunks`` pipelined dispatches; returns (state, metrics of
        the last chunk, stacked [K]). ``on_chunk(state)`` fires after each
        dispatch (step accounting, weight publishing). Pass
        ``final_prefetch=False`` when the caller will ``invalidate()``
        before the next run (avoids staging a chunk only to discard it)."""
        metrics = None
        pending: list = []
        for i in range(n_chunks):
            prefetch = final_prefetch or (i + 1 < n_chunks)
            (batches, w), aux = self._stager.next(prefetch=prefetch)
            state, metrics = self._update(state, batches, w)
            td = metrics.get("td_error") if self._write_back else None
            if td is not None and getattr(td, "is_fully_addressable", False):
                # start the D2H copy now; by flush time the bytes are
                # already local and np.asarray doesn't pay the round trip
                td.copy_to_host_async()
            pending.append((aux, metrics))
            while len(pending) > self._depth:
                self._flush(pending.pop(0))
            if on_chunk is not None:
                on_chunk(state)
        for p in pending:
            self._flush(p)
        return state, metrics

    def _flush(self, pending) -> None:
        aux, metrics = pending
        if aux is None or self._write_back is None:
            return
        td = np.abs(self._fetch_td(metrics)) + 1e-6
        self._write_back(aux, td)
