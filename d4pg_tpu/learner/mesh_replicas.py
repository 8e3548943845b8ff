"""Mesh-native learner replicas: N replicas on ONE mesh, collective merge.

The PR-10 multi-learner plane scales replicas as host threads exchanging
params through the socket aggregator (``learner/aggregator.py`` +
``distributed/update_plane.py``) — correct across hosts, but when the
replicas share one device mesh every round pays a device→host pull, a
0xD4AB frame, host-numpy merge math and a host→device push for data
that never needed to leave the accelerator. This module is the
mesh-native formulation (the "21 minutes" blueprint, arXiv 1801.02852):

- each replica's FULL ``D4PGState`` — params, Adam moments, PRNG key —
  lives as one [N, ...]-stacked tree sharded along the ``replica`` mesh
  axis by partition rule (``partition.replica_stack_shardings``);
- the grad engine is the SAME pure ``fused_chunk_step`` the legacy
  FusedLoop jits, run under ``shard_map`` over the replica axis, so
  each replica trains against its own ring shard with its own key —
  N independent learners in one dispatch;
- the per-round basis pull is device-local: replicas adopt the merged
  params without the tree ever visiting the host;
- the merge itself is a device computation over the replica-sharded
  stack (XLA inserts the gather — no sockets, no host math), with the
  SAME semantics as the host aggregator:

  * ``async`` (IMPACT, arXiv 1912.00167): round-synchronous submissions
    in replica order have lag_i = i, so the fold adopts replica 0
    wholesale and blends replica i at ``w = max(1/(1+i), 1/clip)`` —
    exactly the sequence of ``_blend`` steps the host aggregator applies
    to same-basis submissions arriving in order.
  * ``sync``: N-way average in the widest available dtype (float64 when
    x64 is enabled; the host aggregator always sums in float64, so on
    x64-disabled backends equivalence is tolerance-, not bitwise-grade).
  * N == 1: the merge is a Python-static exact identity — no arithmetic
    touches the params, which is what lets the N=1-through-the-mesh-path
    oracle stay BITWISE against the legacy FusedLoop
    (``tests/test_mesh_replicas.py``).

The socket path remains the cross-host fallback (``--agg_transport``);
this module is for replicas that share a mesh.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from d4pg_tpu.learner.fused import device_replay, fused_chunk_step
from d4pg_tpu.learner.replica import PARAM_FIELDS
from d4pg_tpu.learner.state import D4PGConfig, D4PGState
from d4pg_tpu.parallel import partition, replica_mesh

_tree_map = jax.tree_util.tree_map


def _local(tree):
    """A replica's own slice of a stacked tree, inside the ``shard_map``."""
    return _tree_map(lambda x: x[0], tree)


def _expand(tree):
    return _tree_map(lambda x: x[None], tree)

MODES = ("async", "sync")


def make_collective_merge(n: int, mode: str, clip: float = 8.0):
    """The on-device merge over an [N, ...]-stacked param tree. Pure;
    jit at the call site (the group jits it once with replicated
    out_shardings). Semantics mirror ``Aggregator`` — see module doc."""
    if mode not in MODES:
        raise ValueError(f"unknown aggregation mode {mode!r}")
    if clip < 1.0:
        raise ValueError(f"clip={clip} must be >= 1 (floor 1/clip <= 1)")

    def merge(params: Any) -> Any:
        if n == 1:
            # exact identity — no arithmetic (the N=1 bitwise oracle)
            return _tree_map(lambda x: x[0], params)
        if mode == "sync":
            wide = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            return _tree_map(
                lambda x: (jnp.sum(x.astype(wide), axis=0) / n
                           ).astype(x.dtype),
                params)
        # async: round-synchronous submissions in replica order → lag_i=i
        merged = _tree_map(lambda x: x[0], params)
        for i in range(1, n):
            w = np.float32(max(1.0 / (1.0 + i), 1.0 / clip))
            merged = _tree_map(
                lambda m, x: (m + w * (x[i] - m)).astype(m.dtype),
                merged, params)
        return merged

    return merge


class MeshReplicaGroup:
    """N learner replicas as one replica-sharded program on one mesh.

    ``states`` are the per-replica initial ``D4PGState``s (identical
    nets, decorrelated keys — the same construction train.py uses for
    thread replicas). ``store`` is an optional ``WeightStore``: each
    round's merged params are published through it (``extract`` /
    ``norm_stats`` as in ``Aggregator``), keeping the downstream
    (generation, version) stream identical to the socket path's.

    The fused engine needs ``load(buffer)`` — a host-filled
    ``FusedDeviceReplay`` whose ring/trees are broadcast to every
    replica (each then samples with its OWN key and anneals its OWN
    priorities, the same semantics as N thread replicas over a shared
    service). ``step_host_chunks`` is the service-sampled engine for
    train.py's streaming path.
    """

    def __init__(
        self,
        config: D4PGConfig,
        states: list[D4PGState],
        *,
        k: int,
        batch_size: int,
        mode: str = "async",
        clip: float = 8.0,
        store=None,
        extract: Optional[Callable[[Any], Any]] = None,
        norm_stats: Optional[Callable[[], tuple | None]] = None,
        alpha: float = 0.6,
        beta0: float = 0.4,
        beta_steps: int = 100_000,
        devices=None,
    ):
        self.n = len(states)
        if self.n < 1:
            raise ValueError("need at least one replica state")
        self.mesh = replica_mesh(self.n, devices)
        self._config = config
        self.k = max(1, int(k))
        self._batch_size = int(batch_size)
        self.mode = mode
        self.clip = float(clip)
        self._store = store
        self._extract = extract
        self._norm_stats = norm_stats
        self._alpha = float(alpha)
        self._beta0 = float(beta0)
        self._beta_steps = int(beta_steps)

        self._state_sh = partition.replica_stack_shardings(
            self.mesh, states[0])
        self._state = jax.device_put(
            _tree_map(lambda *xs: jnp.stack(xs), *states), self._state_sh)
        self._storage = None
        self._trees = None
        self._sizes = None
        self._chunk_fns: dict[int, Any] = {}
        self._update_fn = None

        repl = partition.replicated(self.mesh)
        self._merge_fn = jax.jit(
            make_collective_merge(self.n, mode, clip), out_shardings=repl)
        if self.n > 1:
            def adopt(state, merged):
                tiled = {
                    f: _tree_map(
                        lambda x: jnp.broadcast_to(x[None],
                                                   (self.n, *x.shape)),
                        merged[f])
                    for f in PARAM_FIELDS}
                return state._replace(**tiled)

            self._adopt_fn = jax.jit(
                adopt, out_shardings=self._state_sh, donate_argnums=(0,))
        else:
            self._adopt_fn = None

        self.steps_done = 0        # per-replica grad steps
        self.rounds = 0
        self.last_merge_s: Optional[float] = None
        self.last_metrics = None
        self._merged = None        # last merged param tree (device)
        self._versions: list[int] = []

    # -- replay engines ------------------------------------------------------
    def load(self, buffer) -> None:
        """Broadcast a host-filled ``FusedDeviceReplay``'s ring + PER
        trees to every replica ([cap, ...] → [N, cap, ...] sharded over
        ``replica``). The broadcast is one jitted device computation —
        rows are copied over ICI, never through the host."""
        buffer.drain()
        n = self.n
        # no trees under uniform replay: ``None``, an empty pytree
        payload = (buffer.storage, buffer.trees)
        out_sh = partition.replica_stack_shardings(self.mesh, payload)
        # the buffer's arrays are committed to its one device
        # (replay/device_ring.py): hand them to the mesh first
        payload = jax.device_put(payload, partition.replicated(self.mesh))
        # one-shot per load (startup / test fill): jit-with-out_shardings
        # is what materializes the broadcast on every replica's device
        self._storage, self._trees = jax.jit(  # jaxlint: disable=recompile-hazard
            lambda t: _tree_map(
                lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), t),
            out_shardings=out_sh)(payload)
        self._sizes = jax.device_put(
            jnp.full((n,), int(buffer.size), jnp.int32),
            partition.replica_sharding(self.mesh))

    def _chunk_for(self, k: int):
        """The shard_map'd fused chunk for length ``k`` (cached): every
        replica runs the SAME pure ``fused_chunk_step`` the legacy
        FusedLoop jits, against its own shard of the stacked state."""
        if k in self._chunk_fns:
            return self._chunk_fns[k]
        config = self._config
        sample, write_back = device_replay(
            self._batch_size, self._alpha, self._beta0, self._beta_steps)
        R = partition.replica_spec()

        def body(state, trees, storage, size):
            s, t, m = fused_chunk_step(
                config, _local(state), _local(trees), _local(storage),
                size[0], k=k, sample=sample, write_back=write_back)
            return _expand(s), _expand(t), _expand(m)

        fn = shard_map(body, mesh=self.mesh,
                       in_specs=(R, R, R, R), out_specs=(R, R, R),
                       check_vma=False)
        self._chunk_fns[k] = jax.jit(fn, donate_argnums=(0, 1))
        return self._chunk_fns[k]

    def _fused_steps(self, n: int) -> None:
        if self._storage is None:
            raise RuntimeError("fused engine not loaded — call load(buffer)")
        done = 0
        while done < n:
            k = min(self.k, n - done)
            self._state, self._trees, self.last_metrics = self._chunk_for(k)(
                self._state, self._trees, self._storage, self._sizes)
            done += k
        self.steps_done += done

    def step_host_chunks(self, batches, weights=None):
        """The service-sampled engine: one [N, K, B, ...] stack of host
        chunks (replica i trains on ``batches[i]``) through the scanned
        multi-update under ``shard_map``. Returns the stacked metrics
        ([N, K] scalars, [N, K, B] ``td_error`` for the PER write-back).
        """
        from d4pg_tpu.learner.update import multi_update_step

        if self._update_fn is None:
            config = self._config
            R = partition.replica_spec()

            def body(state, batches, w):  # ``w`` None: uniform replay
                s, m = multi_update_step(
                    config, _local(state), _local(batches), _local(w))
                return _expand(s), _expand(m)

            fn = shard_map(body, mesh=self.mesh, in_specs=(R, R, R),
                           out_specs=(R, R), check_vma=False)
            self._update_fn = jax.jit(fn, donate_argnums=(0,))
        stack_sh = partition.replica_sharding(self.mesh)
        self._state, metrics = self._update_fn(
            self._state, jax.device_put(batches, stack_sh),
            jax.device_put(weights, stack_sh))
        self.steps_done += int(batches[0].shape[1])  # [N, K, B, ...] → K
        self.last_metrics = metrics
        return metrics

    # -- the round -----------------------------------------------------------
    def merge(self) -> Any:
        """Run the collective merge over the current per-replica params;
        adopt the result as every replica's next basis (device-local —
        the socket path's per-round pull/push never happens); publish
        through the store when one is attached. Returns the merged
        param tree (device, replicated)."""
        t0 = time.perf_counter()
        stacked = {f: getattr(self._state, f) for f in PARAM_FIELDS}
        merged = self._merge_fn(stacked)
        if self.n > 1:
            # N=1 skips adoption entirely: the merged tree IS replica
            # 0's params, and re-threading it through a device round
            # trip is pointless (the bitwise oracle pins this)
            self._state = self._adopt_fn(self._state, merged)
        jax.block_until_ready(merged)
        self.last_merge_s = time.perf_counter() - t0
        self._merged = merged
        self.rounds += 1
        if self._store is not None:
            pub = self._extract(merged) if self._extract else merged
            norm = self._norm_stats() if self._norm_stats else None
            step = int(np.max(np.asarray(jax.device_get(self._state.step))))
            version = self._store.publish(pub, step=step, to_host=False,
                                          norm_stats=norm)
            self._versions.append(version)
        return merged

    def run_round(self, n: int) -> dict:
        """One round: ``n`` fused grad steps per replica, then the
        collective merge — the mesh-native analog of N thread replicas
        each doing basis-adopt → n steps → submit."""
        self._fused_steps(n)
        self.merge()
        return {"rounds": self.rounds, "steps": self.steps_done,
                "merge_s": self.last_merge_s,
                "version": self._versions[-1] if self._versions else None}

    # -- inspection ----------------------------------------------------------
    def merged_params(self, to_host: bool = True) -> Any:
        """The last merged param tree (None before the first merge)."""
        if self._merged is None:
            return None
        return jax.device_get(self._merged) if to_host else self._merged

    def state_slice(self, i: int) -> D4PGState:
        """Replica ``i``'s state view (device arrays) — oracle tests
        compare its param trees against the legacy loop's."""
        return _tree_map(lambda x: x[i], self._state)

    @property
    def versions(self) -> list[int]:
        return list(self._versions)

    def stats(self) -> dict:
        return {"n": self.n, "mode": self.mode, "rounds": self.rounds,
                "steps": self.steps_done, "merge_s": self.last_merge_s}

    def close(self) -> None:
        self._chunk_fns.clear()
        self._update_fn = None
