"""The fused training loop, extracted from ``train.py``.

One class owns the commit -> dispatch -> stage schedule that used to
live inline in ``train.train_steps_fused`` so the legacy single-learner
path and the multi-learner ``LearnerReplica`` (``learner/replica.py``)
run the SAME implementation instead of a fork — which is what makes the
N=1-replica ⇔ legacy-loop bitwise-equivalence oracle a property of the
code structure rather than a test that merely passed once.

Schedule per fused chunk t (``learner/pipeline.IngestOverlap``):

    ingest.commit()     # block t's ring write+tree insert (async jitted
                        # dispatch, no transfer)
    dispatch chunk t    # K scanned grad steps in ONE device dispatch
    ingest.stage()      # ONE device_put of block t+1, riding under
                        # chunk t's compute
    trace mark_grad     # traces whose rows LANDED before this dispatch
                        # (``buffer.landed``, which ``learner.dispatch``
                        # says too) are consumed: ``grad`` now, ``done``
                        # when the chunk ends on the device

giving ≤ 1 explicit H2D per chunk in steady state. The jitted chunk
fns are cached per remainder size k (the final sub-K chunk of an ``n``
not divisible by K compiles once and is reused).
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from d4pg_tpu.io.profiling import abstract_args
from d4pg_tpu.learner.pipeline import IngestOverlap
from d4pg_tpu.learner.state import D4PGConfig, D4PGState
from d4pg_tpu.obs import startup_log
from d4pg_tpu.obs import trace as obs_trace
from d4pg_tpu.obs.trace import RECORDER as _trace_recorder


class FusedLoop:
    """Drives fused replay+learn chunks against a device-resident buffer.

    ``buffer`` is a ``FusedDeviceReplay``/``ShardedFusedReplay`` (needs
    ``.storage``, ``.size`` and ``.trees``, ``None`` under uniform
    replay). ``service``
    is the owning ``ReplayService`` when actor rows stream in between
    chunks (the loop claims the service's single ingest-dispatch slot
    via ``IngestOverlap``); ``None`` runs the loop against a statically
    filled buffer (tests, the N=1 oracle)."""

    def __init__(
        self,
        config: D4PGConfig,
        buffer,
        *,
        k: int,
        batch_size: int,
        prioritized: bool = True,
        alpha: float = 0.6,
        beta0: float = 0.4,
        beta_steps: int = 100_000,
        mesh=None,
        service=None,
        donate: bool = True,
    ):
        self._config = config
        self._buffer = buffer
        self.k = max(1, int(k))
        self._batch_size = int(batch_size)
        # the buffer says whether replay is prioritized (its ``trees``);
        # the argument is kept for its callers and held to that
        if bool(prioritized) != (buffer.trees is not None):
            raise ValueError(
                f"prioritized={prioritized} against a buffer "
                f"{'with' if buffer.trees is not None else 'without'} trees")
        self._alpha = float(alpha)
        self._beta0 = float(beta0)
        self._beta_steps = int(beta_steps)
        self._mesh = mesh
        self._donate = bool(donate)
        self._fns: dict[int, object] = {}
        self.ingest = IngestOverlap(service) if service is not None else None
        self.steps_done = 0
        self.chunks = 0
        self._tabled = False  # the chunk program is in the program table

    def fused_for(self, k: int):
        """The jitted fused-chunk fn for chunk length ``k`` (cached)."""
        if k not in self._fns:
            from d4pg_tpu.learner.fused import make_fused_chunk

            self._fns[k] = make_fused_chunk(
                self._config, k=k, batch_size=self._batch_size,
                alpha=self._alpha, beta0=self._beta0,
                beta_steps=self._beta_steps, mesh=self._mesh,
                donate=self._donate)
        return self._fns[k]

    def run(
        self,
        state: D4PGState,
        n: int,
        on_chunk: Optional[Callable[[D4PGState, int], None]] = None,
    ):
        """``n`` fused grad steps from ``state``; returns ``(state,
        metrics)`` with the LAST chunk's metrics stacked [k] (``None``
        when ``n <= 0``). ``on_chunk(state, k)`` fires after each
        dispatch — step accounting and weight publishing live with the
        caller, which is what lets the legacy path and a replica share
        this loop while publishing through different stores."""
        with obs_trace.span("learner.run", n=n):
            return self._run(state, n, on_chunk)

    def _run(self, state, n, on_chunk):
        # Host spans (obs/trace.span -> the profiler's trace, nested by
        # thread): learner.run > learner.flush and, per chunk,
        # learner.chunk > ingest.commit, learner.dispatch, ingest.stage,
        # learner.on_chunk. ``chunk=`` is what the spans of one chunk
        # share; PERF.md section 3 says which metric reads which.
        buffer = self._buffer
        metrics = None
        done = 0
        home = getattr(buffer, "home", None)
        if home is not None:
            # the one-device buffer's arrays are committed to its device
            # (replay/device_ring.py), so what a chunk returns is too: a
            # state that went in uncommitted would make the second chunk
            # another program. No copy: the same buffers, committed.
            import jax

            state = jax.device_put(state, home)
        if self.ingest is not None:
            # cycle boundary: every staged row lands before training
            with obs_trace.span("learner.flush") as flush:
                flush.set_metadata(rows=self.ingest.flush())
        while done < n:
            k = min(self.k, n - done)
            fn = self.fused_for(k)
            chunk = self.chunks
            with obs_trace.span("learner.chunk", chunk=chunk, k=k):
                if self.ingest is not None:
                    self.ingest.commit()
                args = (state, buffer.trees, buffer.storage, buffer.size)
                first = obs_trace.NULL_SPAN
                if not self._tabled:  # first dispatch: enter the table
                    obs_trace.register_program("learner.chunk", fn,
                                     abstract_args(args))
                    self._tabled = True
                    # the call that traces, lowers and compiles (or loads)
                    # the program: a phase of the start-up log
                    first = obs_trace.span("learner.first_dispatch",
                                           program="learner.chunk")
                # the jitted call alone: where the host blocks once the
                # runtime's queue of programs in flight is full. ``landed``:
                # the position in host staging up to which rows are in the
                # ring this chunk samples (what ``ingest.commit`` above and
                # the flush before it have dispatched; 0 from the sharded
                # buffer, which keeps no positions).
                landed = buffer.landed
                with first, obs_trace.span("learner.dispatch", chunk=chunk,
                                           landed=landed):
                    state, buffer.trees, metrics = fn(*args)
                if first is not obs_trace.NULL_SPAN:
                    # start-up is over: the log's import hook comes out
                    startup_log.LOG.unwatch_imports()
                del args  # state and trees were donated
                if self.ingest is not None:
                    self.ingest.stage()
                # traces whose rows landed before this dispatch are now
                # consumed; near-free no-op when nothing is pending
                _trace_recorder.mark_grad(landed=landed,
                                          done=metrics["critic_loss"])
                done += k
                self.steps_done += k
                self.chunks += 1
                if on_chunk is not None:
                    with obs_trace.span("learner.on_chunk", chunk=chunk):
                        on_chunk(state, k)
        return state, metrics

    def close(self) -> None:
        """Release the service's ingest-dispatch slot so a successor
        consumer (a respawned replica) can claim it."""
        if self.ingest is not None:
            self.ingest.release()


class DealtLoop:
    """Drives pre-sampled dealt blocks from a ``DealtBlockRing`` — the
    consumer half of the sample-on-ingest plane (``replay/sampler.py``).

    Mirrors ``FusedLoop.run``'s contract (state in, ``(state, metrics)``
    out, ``on_chunk`` callback) so ``LearnerReplica`` treats both
    pre-sampled paths uniformly. Per block:

        ring.pop()                  # leaf-tier wait — NO buffer lock
        dispatch K scanned steps    # block rows + dealer IS weights
        service.queue_writeback()   # TD priorities, gen-fenced, drained
                                    # by the owning ingest shard
        trace mark_grad             # deal->grad span terminal

    The grad loop never acquires the buffer lock: sampling already
    happened on the commit thread, and the write-back only enqueues
    under the ``sampler`` tier. ``stop`` (an ``Event``) lets the owning
    replica abandon a blocked pop mid-round on kill.

    Device-dealt blocks (``replay/device_sampler.DeviceSampleDealer``)
    arrive with ``batches``/``weights``/``idx``/``gen`` as DEVICE
    arrays: the rows feed ``update_fn`` with no host round-trip, and
    the loop materializes only ``idx``/``gen`` (``[K, B]`` int arrays,
    not sampled rows) on the host at write-back time — the one
    deliberate D2H on the grad side, synced here so the cost is
    attributed to the write-back and not hidden inside the dealer's
    settle. ``td_error`` comes back from the update anyway; the same
    ``np.asarray`` covers both paths.
    """

    def __init__(self, update_fn, ring, service, *,
                 stop=None, pop_timeout: float = 0.2):
        self._update = update_fn
        self._ring = ring
        self._service = service
        self._stop = stop
        self._pop_timeout = float(pop_timeout)
        self.steps_done = 0

    def run(
        self,
        state: D4PGState,
        n: int,
        on_chunk: Optional[Callable[[D4PGState, int], None]] = None,
    ):
        """At least ``n`` grad steps from dealt blocks (blocks arrive in
        dealer-sized chunks of K, so the final block may overshoot);
        returns ``(state, metrics)`` with the LAST block's stacked-[k]
        metrics (``None`` when nothing was consumed — closed ring)."""
        metrics = None
        done = 0
        while done < n and (self._stop is None or not self._stop.is_set()):
            block = self._ring.pop(timeout=self._pop_timeout)
            if block is None:
                if self._ring.closed:
                    break
                continue
            state, metrics = self._update(
                state, block.batches, block.weights)
            td = np.abs(np.asarray(metrics["td_error"])) + 1e-6
            # One explicit host sync for device-dealt blocks (no-op
            # copies for host blocks): [K, B] ints, never sampled rows.
            idx = np.asarray(block.idx)
            gen = np.asarray(block.gen)
            self._service.queue_writeback(idx, td, gen)
            _trace_recorder.mark_grad()
            k = int(idx.shape[0])
            done += k
            self.steps_done += k
            if on_chunk is not None:
                on_chunk(state, k)
        return state, metrics
