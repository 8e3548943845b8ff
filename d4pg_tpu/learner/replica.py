"""One learner replica: owns its shard of training state, nothing else.

A ``LearnerReplica`` is the unit the multi-learner plane scales
(``--learners N``): it holds a FULL ``D4PGState`` — network params plus
its OWN optimizer state and PRNG key — but the network params are only a
working copy of the aggregator's authoritative tree. Each round it

    1. pulls a **basis** from the aggregator (version-stamped; params
       arrive only when someone else advanced the aggregate — a replica
       never re-adopts its own round-tripped submission),
    2. runs ``n`` grad steps against replay (fused device loop when it
       is the sole consumer, host-sampled chunks otherwise),
    3. submits its resulting params stamped with the basis version, so
       the aggregator can weight the update by how stale it is
       (``learner/aggregator.py``).

Optimizer state and key deliberately do NOT flow through the aggregator:
IMPACT-style correction is defined on parameters; each replica's Adam
moments chase its own trajectory (standard in async SGD — see the
module doc in ``aggregator.py``).

Two sampling modes, chosen by what the replica is given:

- **fused** (``buffer`` passed; ``service`` optionally rides along for
  the ingest overlap): the extracted ``FusedLoop`` —
  commit/dispatch/stage against a device-resident buffer. Single
  consumer by construction (``IngestOverlap`` enforces it), so train.py
  only builds fused replicas at N=1 — which is exactly the
  configuration the bitwise legacy-equivalence oracle pins.
- **host** (``service`` passed): ``ReplayService.sample_chunk`` under
  the service's own buffer lock (thread-safe for N concurrent
  replicas) + ``make_multi_update`` K-scanned dispatch + deferred PER
  priority write-back with the generation guard.
- **dealt** (``dealt_ring`` passed with ``service``): the
  sample-on-ingest plane (``replay/sampler.py``) — the replica pops
  ready-to-train blocks (rows + IS weights, pre-sampled by the commit
  thread's dealer) from its bounded ring and feeds TD priorities back
  through ``service.queue_writeback``. The sample path acquires the
  ring leaf lock and the ``sampler`` tier ONLY — never the buffer
  lock, which is the whole point. The replica is agnostic to WHERE
  the dealer sampled: host blocks (``SampleDealer``, numpy rows) and
  device blocks (``replay/device_sampler.DeviceSampleDealer``,
  device-resident gathers that flow into ``update_fn`` with no host
  round-trip) ride the same ring and the same ``DealtLoop`` — the
  commit thread owns every device handle in the device-dealt mode, so
  nothing here changes per variant.

PER beta annealing: with N replicas each replica annealing off its own
``steps_done`` would scale the anneal rate with N (the PR-10 defect) —
pass one shared ``replay/schedule.SharedBetaSchedule`` as
``beta_schedule`` and every replica reads the same global clock. When
omitted, a private schedule reproduces the legacy single-replica
behavior bitwise.

Locking: ``_replica_lock`` (tier ``replica`` = 36) guards ONLY control
state — counters, epoch, stop flag. It is never held across sampling,
the grad loop, or ``submit`` — replay's buffer lock sits ABOVE it
(``buffer`` = 40), so holding it into a sample would be an ascent the
runtime sentinels reject at the first acquisition. Holding it into
``submit`` would be legal (``agg`` = 34 descends) but pointless.
"""

from __future__ import annotations

import threading
from typing import Any, Optional

import jax
import numpy as np

from d4pg_tpu.core.locking import TieredLock
from d4pg_tpu.learner.loop import FusedLoop
from d4pg_tpu.learner.state import D4PGConfig, D4PGState

PARAM_FIELDS = ("actor_params", "critic_params",
                "target_actor_params", "target_critic_params")


def params_of(state: D4PGState, to_host: bool = True) -> dict:
    """The aggregation tree: all four network-param subtrees (targets
    included — averaging live nets but not targets would tear the
    distributional TD bootstrap apart across replicas)."""
    tree = {f: getattr(state, f) for f in PARAM_FIELDS}
    return jax.device_get(tree) if to_host else tree


def adopt_params(state: D4PGState, params: dict) -> D4PGState:
    """A new basis from the aggregator, keeping THIS replica's optimizer
    state, PRNG key and step counter."""
    return state._replace(**{f: params[f] for f in PARAM_FIELDS})


class LearnerReplica:
    """See module doc. ``agg`` is anything with the ``Aggregator`` duck
    type (register/basis/submit) — the in-process aggregator in train.py,
    or an ``update_plane.UpdateClient`` speaking the wire protocol."""

    def __init__(
        self,
        replica_id: int,
        config: D4PGConfig,
        agg,
        state: D4PGState,
        *,
        k: int,
        batch_size: int,
        prioritized: bool = True,
        alpha: float = 0.6,
        beta0: float = 0.4,
        beta_steps: int = 100_000,
        buffer=None,
        service=None,
        dealt_ring=None,
        beta_schedule=None,
        mesh=None,
        donate: bool = True,
    ):
        if buffer is None and service is None:
            raise ValueError(
                "need buffer= (fused mode, sole consumer; service= "
                "optionally adds the ingest overlap) or service= alone "
                "(host-sampled mode, N-replica safe; add dealt_ring= "
                "for the sample-on-ingest dealt mode)")
        if dealt_ring is not None and (buffer is not None or service is None):
            raise ValueError("dealt mode needs service= (for the priority "
                             "write-back) and no fused buffer=")
        if dealt_ring is not None and not prioritized:
            raise ValueError(
                "dealt mode is PER-only: dealt blocks carry IS weights")
        self.replica_id = int(replica_id)
        self._config = config
        self._agg = agg
        self._state = state
        if buffer is not None:
            self.mode = "fused"
        elif dealt_ring is not None:
            self.mode = "dealt"
        else:
            self.mode = "host"
        self.k = max(1, int(k))
        self._batch_size = int(batch_size)
        self._prioritized = bool(prioritized)
        self._beta0 = float(beta0)
        self._beta_steps = int(beta_steps)
        self._service = service
        self._dealt_ring = dealt_ring
        # shared anneal clock (see module doc); private fallback keeps
        # the legacy single-replica anneal bitwise
        from d4pg_tpu.replay.schedule import SharedBetaSchedule
        self._beta_sched = beta_schedule or SharedBetaSchedule(
            beta0=self._beta0, beta_steps=self._beta_steps)
        self._loop = None
        self._update = None
        if self.mode == "fused":
            self._loop = FusedLoop(
                config, buffer, k=self.k, batch_size=batch_size,
                prioritized=prioritized, alpha=alpha, beta0=beta0,
                beta_steps=beta_steps, mesh=mesh, service=service,
                donate=donate)
        else:
            from d4pg_tpu.learner.update import make_multi_update
            self._update = make_multi_update(config, donate=donate)
        # control state ONLY under this lock (see module doc)
        self._replica_lock = TieredLock("replica")
        self._stop = threading.Event()
        self._dealt_loop = None
        if self.mode == "dealt":
            from d4pg_tpu.learner.loop import DealtLoop
            self._dealt_loop = DealtLoop(
                self._update, dealt_ring, service, stop=self._stop)
        self.epoch = agg.register(self.replica_id,
                                  params=params_of(state), step=0)
        self.steps_done = 0
        self.last_metrics = None  # last chunk's stacked-[k] metrics dict
        self.rounds = 0
        self.applied = 0
        self.fenced = 0
        self.last_lag: Optional[int] = None
        self.last_status = "idle"

    # -- sampling/update paths ----------------------------------------------
    def _host_steps(self, n: int) -> None:
        svc = self._service
        done = 0
        # ONE clock read for the whole call: beta is constant across the
        # call's chunks (the legacy per-chunk ``_beta()`` was too, since
        # ``steps_done`` only advanced after the loop) and two replicas
        # at the same global step compute the identical value.
        beta = self._beta_sched.beta_at(self._beta_sched.current_step())
        while done < n and not self._stop.is_set():
            k = min(self.k, n - done)
            if self._prioritized:
                batches, w, idx, gen = svc.sample_chunk(
                    k, self._batch_size, beta=beta,
                    weight_base=svc.weight_base())
                self._state, metrics = self._update(self._state, batches, w)
                td = np.abs(np.asarray(metrics["td_error"])) + 1e-6
                svc.update_priorities(idx, td, generation=gen)
            else:
                batches, _w, _idx, _gen = svc.sample_chunk(
                    k, self._batch_size)
                self._state, metrics = self._update(self._state, batches,
                                                    None)
            self.last_metrics = metrics
            done += k
        if done:
            self._beta_sched.advance(done)
        self.steps_done += done

    def _dealt_steps(self, n: int) -> None:
        """Consume pre-sampled blocks through the extracted ``DealtLoop``
        (``learner/loop.py``): pop, K-chunk update, queue the TD
        write-back. No buffer-lock acquisition anywhere on this path —
        the ring pop is a leaf-tier wait and the write-back enqueues
        under the ``sampler`` tier (beta already rode in with the block,
        annealed by the dealer's shared clock)."""
        before = self._dealt_loop.steps_done
        self._state, metrics = self._dealt_loop.run(self._state, n)
        if metrics is not None:
            self.last_metrics = metrics
        self.steps_done += self._dealt_loop.steps_done - before

    def _fused_steps(self, n: int) -> None:
        self._state, metrics = self._loop.run(self._state, n)
        if metrics is not None:
            self.last_metrics = metrics
        self.steps_done += n

    # -- the replica round ---------------------------------------------------
    def run_round(self, n: int, generation: int | None = None) -> dict:
        """One basis-adopt -> n grad steps -> version-stamped submit
        cycle; returns the aggregator's verdict (applied/fenced + lag +
        weight). No replica lock is held across any of it."""
        basis_version, basis = self._agg.basis(self.replica_id)
        if basis is not None:
            self._state = adopt_params(self._state, basis)
        if self.mode == "fused":
            self._fused_steps(n)
        elif self.mode == "dealt":
            self._dealt_steps(n)
        else:
            self._host_steps(n)
        result = self._agg.submit(
            self.replica_id, self.epoch, params_of(self._state),
            basis_version, step=self.steps_done, generation=generation)
        with self._replica_lock:
            self.rounds += 1
            self.last_status = result["status"]
            self.last_lag = result.get("lag")
            if result["status"] == "applied":
                self.applied += 1
            elif result["status"] == "fenced":
                self.fenced += 1
        return result

    def run(self, rounds: int, steps_per_round: int) -> None:
        """Supervisor-thread entry: rounds until done or stopped."""
        for _ in range(rounds):
            if self._stop.is_set():
                return
            self.run_round(steps_per_round)

    def respawn(self) -> int:
        """Supervisor path after a crash: fence the dead epoch (so an
        in-flight submission from the corpse bounces on arrival), then
        re-register at the next epoch. The replica keeps its state —
        it is the thread that died, not the params."""
        self._agg.fence_replica(self.replica_id)
        self.epoch = self._agg.register(self.replica_id)
        self._stop.clear()
        return self.epoch

    # -- control -------------------------------------------------------------
    def stop(self) -> None:
        self._stop.set()

    def stopped(self) -> bool:
        return self._stop.is_set()

    @property
    def state(self) -> D4PGState:
        return self._state

    def stats(self) -> dict:
        with self._replica_lock:
            return {"replica": self.replica_id, "mode": self.mode,
                    "epoch": self.epoch, "steps": self.steps_done,
                    "rounds": self.rounds, "applied": self.applied,
                    "fenced": self.fenced, "lag": self.last_lag,
                    "status": self.last_status}

    def close(self) -> None:
        self._stop.set()
        if self._loop is not None:
            self._loop.close()
