"""Device-resident PER sampling: the stratified descent fused onto the
commit dispatch.

PR 12 (``replay/sampler.SampleDealer``) moved sampling off the learner
onto the commit thread, but the draw still walks HOST trees and the
sampled rows still round-trip through host RAM — the host-side sampling
bottleneck "In-Network Experience Sampling" (PAPERS.md, arXiv
2110.13506) measures as the dominant ingest cost. This module finishes
the move: the sum/min trees stay the DEVICE arrays the fused commit
already maintains (``replay/fused_buffer.FusedDeviceReplay`` in
``gen_tracked`` mode), the seeded stratified descent runs on device
immediately after the commit dispatch, and dealt blocks are emitted as
device-resident gathers — zero host tree math, zero sampled-row H2D
(TransferSentinel-checked in tests/test_devsample.py), and the replica
sample path keeps PR 12's zero buffer-lock acquisitions (ring pop +
sampler-tier write-back enqueue only).

Division of labor per ``ingest_and_deal`` tick (commit thread, inside
the ONE buffer-lock window the commit already owned):

  1. mirror the tick's inserts into the HOST bookkeeping (generation
     fence, ticket seqs, trace ids) — index arithmetic, no tree math;
  2. ``buffer.drain()``: the fused commit dispatch lands the staged rows
     AND their entry priorities (``max_priority ** alpha``, computed on
     the host in float64 and cast float32) AND bumps the device
     generation array;
  3. settle queued priority write-backs: generation-fenced on the host
     mirror, last-wins deduplicated (XLA leaves duplicate-scatter
     winners unspecified; numpy fancy assignment — the twin — is
     last-wins), padded to a fixed bucket, ONE jitted scatter into the
     device trees;
  4. draw: unit uniforms from the dealer's seeded HOST stream (the
     bitwise-oracle stream; skipped-before-RNG backpressure rules are
     inherited unchanged), then ONE jitted deal dispatch — strata mass,
     descent, row gather, leaf-priority gather, generation snapshot —
     plus the shared weight transform (``device_per.block_weights``).

Bitwise oracle: with the same seed and insert/write-back order, blocks
equal ``SampleDealer(scheme='device')`` — the float32 HOST twin — in
``(idx, weights, beta, rows, gen)`` exactly (tests/test_devsample.py).
The twin-vs-float64-legacy relation is pinned separately on
dyadic-rational priorities, where float32 and float64 trees agree
exactly. What is NOT preserved from the float64 host dealer is the
rounding of tree aggregates for arbitrary priorities — a documented
consequence of float32 device trees, not of the descent logic (the tie
rule ``mass >= left_sum`` -> RIGHT is shared by every implementation,
see ``device_per.descend``).

The descent is ``device_per.descend`` (the gather descent the fused chunk
runs); the PR-12 host dealer is the other ``--sampler`` arm, constructed
by the caller, and :func:`resolve_sampler` says which one ``auto`` means.
Host->device bytes on the deal path are the [K, B]
float32 uniforms and two scalars per block — O(K*B) floats against the
O(K*B*obs_dim) row bytes the host dealer ships, and none of it an
explicit ``device_put`` of sampled rows.

Trace spans: sampled indices never visit the host (the audit mode below
is the chaos-only exception), so the ``deal`` span is stamped on the
NEWEST COMMITTED insert's trace id rather than the newest sampled
constituent — still a real, committed frame (commit_to_deal >= 0), still
zero-orphan. ``audit=True`` pays one explicit per-deal D2H of the
sampled indices to run the dead-ticket cross-check; it is a chaos-rig
knob, never a shipped-path default.
"""

from __future__ import annotations

import time

import numpy as np

from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.sampler import DealtBlock, SampleDealer

def resolve_sampler(flag: str) -> str:
    """The ``--sampler`` arm a flag means. ``'scan'`` (this module's
    dealer) and ``'host'`` (``SampleDealer``) pass through; ``'auto'`` is
    ``'scan'`` on a TPU, where the descent rides the commit dispatch the
    trees already live behind, and ``'host'`` elsewhere, where a dispatch
    per deal saturates the commit thread (``docs/evidence/fleet/``)."""
    if flag == "auto":
        import jax

        return "scan" if jax.default_backend() == "tpu" else "host"
    if flag not in ("scan", "host"):
        raise ValueError(f"unknown --sampler arm {flag!r} "
                         "(want 'auto', 'scan' or 'host')")
    return flag


# Write-back scatter bucket: settles pad (idx = tree capacity, dropped)
# or split to this many rows so the jitted scatter compiles ONCE.
_WB_BUCKET = 2048


class DeviceSampleDealer(SampleDealer):
    """``SampleDealer`` with the sample path on the device.

    Drop-in for the host dealer at every ``ReplayService`` touchpoint
    (``attach_dealer``/``ingest_and_deal``/``publish``/
    ``queue_writeback``/``resync``/``close``); requires the buffer to be
    a ``FusedDeviceReplay(gen_tracked=True)``. Single-writer discipline
    tightens to: the COMMIT THREAD owns every device handle (storage,
    trees, generation array) — stage, commit, deal and write-back
    dispatches all run inside its buffer-lock windows, which is why
    :meth:`drain_writebacks_for_shard` is a no-op here (settles ride the
    commit/idle ticks instead of shard workers; there is no host tree to
    shard-own). Replicas still only ever enqueue write-backs under the
    ``sampler`` tier.

    The inherited host slice trees stay empty (float32, ~16 bytes/slot;
    the geometry still routes write-back queues and sizes the
    generation mirror) — the authoritative trees are the buffer's device
    arrays.
    """

    # The attached service's commit thread is the ONLY ingest-dispatch
    # driver: this dealer drains the staged slot inside every
    # ingest's buffer-lock window. learner/pipeline.IngestOverlap
    # checks this flag and refuses to claim the slot.
    owns_commit = True

    def __init__(self, capacity: int, rings, *, k: int, batch_size: int,
                 alpha: float = 0.6, beta_schedule=None, min_size: int = 1,
                 seed: int = 0, ring_capacity: int = 4,
                 max_deals_per_tick: int = 1, audit: bool = False):
        super().__init__(capacity, rings, n_shards=1, k=k,
                         batch_size=batch_size, alpha=alpha,
                         beta_schedule=beta_schedule, min_size=min_size,
                         seed=seed, ring_capacity=ring_capacity,
                         max_deals_per_tick=max_deals_per_tick,
                         audit=audit, scheme="device")
        self._buffer = None
        self._deal_fn = self._make_deal()

    # -- the fused deal dispatch -------------------------------------------
    def _make_deal(self):
        import jax
        import jax.numpy as jnp

        treecap = self._trees.capacity  # next_pow2(ring capacity)

        def deal(storage, sum_tree, min_tree, gen, u, size):
            total = sum_tree[1]
            mass = dper.strata_mass(u, total)  # [K, B] float32
            idx = dper.descend(sum_tree, mass)
            idx = jnp.minimum(idx, jnp.maximum(size - 1, 0))
            # device-resident gathers: the dealt rows never exist on the
            # host (DealtBlock.batches are device arrays [K, B, ...])
            rows = jax.tree_util.tree_map(lambda a: a[idx], storage)
            leaf_p = sum_tree[treecap + idx]
            gen_blk = gen[idx]
            return rows, idx, leaf_p, gen_blk, total, min_tree[1]

        return jax.jit(deal)

    @property
    def deal_fn(self):
        """The jitted deal dispatch — exposed so harnesses and tests can run
        ``ReshardSentinel.inspect`` over its compiled HLO (the fused
        sample dispatch must contain 0 resharding collectives)."""
        return self._deal_fn

    # -- commit-thread hooks (sampler lock held, buffer lock above it) ------
    def _apply_insert_locked(self, idx: np.ndarray) -> None:
        # entry priorities land in the DEVICE trees via the fused commit
        # (_post_ingest_locked drains); the host slice trees stay empty
        pass

    def _post_ingest_locked(self, buffer) -> None:
        self._buffer = buffer
        # land every staged row + entry priority + generation bump NOW,
        # in the same buffer-lock window as the adds: slot pre-assignment
        # order (buffer.add) == commit order, the invariant gen_tracked
        # mode is built on
        buffer.drain()

    def _settle_locked(self, owner: int | None = None) -> None:
        buffer = self._buffer
        if buffer is None or self._wb_depth == 0:
            return
        idx_parts, pri_parts = [], []
        for q in self._wb:
            while q:
                idx, pri, gen, t_enq = q.popleft()
                self._wb_depth -= 1
                self._wb_lag.observe(1e3 * (time.monotonic() - t_enq))
                live = self._gen[idx] == gen
                if not live.all():
                    # counter bump is guarded by the caller: base
                    # ingest_and_deal holds the sampler lock across
                    # every _settle_locked call
                    self.writeback_dropped_stale += int((~live).sum())  # jaxlint: guarded-by=_sampler_lock
                    idx, pri = idx[live], pri[live]
                if len(idx):
                    idx_parts.append(idx)
                    pri_parts.append(pri)
        if not idx_parts:
            return
        idx = np.concatenate(idx_parts)
        pri = np.concatenate(pri_parts)
        # last-wins dedup in queue order: numpy fancy assignment (the
        # host twin) keeps the LAST duplicate write; XLA scatter leaves
        # the winner unspecified, so the duplicates must never reach it
        last = {int(s): j for j, s in enumerate(idx)}
        keep = np.fromiter(last.values(), np.int64, len(last))
        idx_u = idx[keep]
        # host float64 pow, float32 cast — the same rounding the twin's
        # trees.set applies, so both trees hold identical leaf bits
        p_u = (pri[keep] ** self.alpha).astype(np.float32)
        treecap = self._trees.capacity
        for c0 in range(0, len(idx_u), _WB_BUCKET):
            ci = idx_u[c0:c0 + _WB_BUCKET].astype(np.int32)
            cp = p_u[c0:c0 + _WB_BUCKET]
            if len(ci) < _WB_BUCKET:  # pad rows park at treecap: dropped
                pad = _WB_BUCKET - len(ci)
                ci = np.concatenate([ci, np.full(pad, treecap, np.int32)])
                cp = np.concatenate([cp, np.zeros(pad, np.float32)])
            buffer.apply_priorities(ci, cp)
        self.max_priority = max(self.max_priority, float(pri.max()))
        # the buffer's host scalar feeds the NEXT commit's p_ins operand
        buffer.max_priority = self.max_priority

    def _draw_block_locked(self, buffer):
        # priorities are strictly positive in the dealt plane (entry
        # p_ins > 0, write-backs assert > 0), so size > 0 <=> total > 0
        # — the host guard without a device sync
        size = self._size
        if size <= 0:
            return None
        t = self._beta.current_step()
        beta = self._beta.beta_at(t)
        # K*B doubles off the seeded host stream, cast f32 — the same
        # consumption (count AND values) as K twin strata draws
        u = self._rng.uniform(0.0, 1.0, (self.k, self.batch_size)).astype(
            np.float32)
        rows, idx, leaf_p, gen_blk, total, min_root = self._deal_fn(
            buffer.storage, buffer.trees.sum_tree, buffer.trees.min_tree,
            buffer.gen, u, np.int32(size))
        w = dper.block_weights_jitted(total, min_root, leaf_p,
                                      np.float32(beta), np.int32(size))
        if self._audit and self._dead:
            # audit is the one deliberate D2H on this path (chaos only):
            # the dead-ticket cross-check needs the sampled slots' seqs
            flat = np.asarray(idx).ravel()
            hits = {int(s) for s in self._src_seq[flat]} & self._dead
            self.dealt_dead_tickets += len(hits)  # jaxlint: guarded-by=_sampler_lock
        tid = self._last_tid  # newest committed insert (module docstring)
        self._beta.advance(self.k)
        self._deal_seq += 1
        self.dealt_blocks += 1  # jaxlint: guarded-by=_sampler_lock
        self.dealt_rows += self.k * self.batch_size  # jaxlint: guarded-by=_sampler_lock
        return DealtBlock(rows, w, idx, gen_blk, beta, t, tid,
                          self._deal_seq)

    # -- shard-worker side --------------------------------------------------
    def drain_writebacks_for_shard(self, shard_idx: int) -> None:
        """No-op: device tree writes belong to the commit thread (the
        single owner of the device handles); settles ride its commit and
        idle ticks instead of shard workers."""

    # -- lifecycle ----------------------------------------------------------
    def resync(self, buffer) -> None:
        """Adopt ``buffer``'s device PER state (attach / restore). The
        trees stay where they are — in the buffer — so unlike the host
        dealer there is nothing to rebuild; only the host mirrors
        (generation fence, max_priority, bookkeeping) re-derive."""
        if not getattr(buffer, "gen_tracked", False):
            raise ValueError(
                "DeviceSampleDealer needs a FusedDeviceReplay("
                "gen_tracked=True) buffer — the deal dispatch reads its "
                "device trees and generation array")
        with self._sampler_lock:
            self._buffer = buffer
            self._size = int(buffer.size)
            self.max_priority = float(buffer.max_priority)
            self._gen = np.asarray(buffer.generation).copy()
            self._src_seq.fill(-1)
            self._tid_of.fill(0)
            self._ins_seq.fill(0)
            self._last_tid = 0
            for q in self._wb:
                q.clear()
            self._wb_depth = 0
