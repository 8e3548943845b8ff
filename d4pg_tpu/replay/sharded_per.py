"""Sharded device-resident replay: ring + PER trees distributed over the
learner mesh's ``data`` axis.

The multi-chip extension of the fused replay path (``device_ring.py`` /
``device_per.py`` hold everything on ONE device). Here every device of
the data axis owns a shard of the transition ring and its own PER
sum/min tree pair; sampling, gathering and priority write-back run
per-shard inside the sharded learner dispatch (``learner/fused.py``'s
``make_fused_chunk(mesh=...)``) — so the production configuration
(K-step scan x data parallelism) keeps ZERO per-chunk host round trips
and the batch rows never cross devices (each shard contributes
``B / n_shards`` rows; only gradients ride the ICI collectives).

This is the Ape-X sharded-replay layout made device-native. Sampling
semantics: each shard draws B/N proportional samples from ITS shard
(stratified across shards by construction); the importance weights
correct for the true per-draw probability ``(1/N) * p_i / total_h``
with a GLOBAL max-weight normalizer computed by ``lax.pmin`` over the
data axis — reducing exactly to the reference formula
(``prioritized_replay_memory.py:299-313``) at N=1.

Host-side bookkeeping mirrors ``fused_buffer.FusedDeviceReplay``:
``add`` stages rows (bounded), ``drain`` flushes at chunk boundaries on
the learner thread (single owner of the donated device handles),
splitting rows round-robin so shard sizes stay balanced.

MULTI-HOST: the same buffer runs over a global (multi-process) mesh —
the production pod shape the reference approximates with one host's
shared memory (``main.py:371-405``). Each host owns the data-axis
shards of its LOCAL devices (the Ape-X layout: rows never cross hosts;
only gradients and the one ``pmin`` scalar ride DCN). Host-side state
(`_head`/`_size`/staging) covers only the owned shards; ``drain`` and
``state_dict`` become collective calls — every host participates in the
same SPMD insert with a globally-agreed pad width (one tiny allgather),
and checkpoints hold each host's own shard-set (restored via the
per-host sidecar scheme in ``train.py``).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from d4pg_tpu.replay.segment_tree import next_pow2
from d4pg_tpu.replay.uniform import TransitionBatch, pack_rows, validate_rows


def _owned_data_rows(mesh) -> tuple[list[int], bool]:
    """Global data-axis indices whose devices ALL belong to this process,
    and whether the mesh spans any remote devices at all. A data row split
    across processes cannot host a replay shard (its ring rows would need
    cross-host writes), so that layout is rejected outright."""
    import jax

    from d4pg_tpu.parallel.mesh import DATA_AXIS

    axis = mesh.axis_names.index(DATA_AXIS)
    rows = np.moveaxis(mesh.devices, axis, 0)
    me = jax.process_index()
    owned, remote = [], False
    for i in range(rows.shape[0]):
        procs = {d.process_index for d in rows[i].flat}
        if procs == {me}:
            owned.append(i)
        else:
            remote = True
            if me in procs:
                raise ValueError(
                    f"data-axis row {i} is split across processes "
                    f"{sorted(procs)}; replay shards must be host-local "
                    "(put the model axis within a host)")
    return owned, remote


class ShardedPerTrees(NamedTuple):
    """Per-shard tree pair, leading axis = shard (sharded over ``data``)."""

    sum_tree: "jax.Array"  # [n_shards, 2 * cap_shard]
    min_tree: "jax.Array"  # [n_shards, min_tree_nodes(cap_shard)]
    max_priority: "jax.Array"  # [n_shards] per-shard running max

    @property
    def cap_shard(self) -> int:
        return self.sum_tree.shape[1] // 2


class ShardedFusedReplay:
    """Device-sharded ring + trees for the mesh fused learner path."""

    # no host staging stream, so no positions (fused_buffer.py): rows are in
    # the ring when ``add`` returns, and ``learner.dispatch`` says 0
    landed = 0

    def __init__(
        self,
        capacity: int,
        obs_dim: int | tuple,
        act_dim: int,
        mesh,
        alpha: float = 0.6,
        prioritized: bool = True,
        obs_dtype=None,
    ):
        import jax
        import jax.numpy as jnp

        from d4pg_tpu.parallel import partition
        from d4pg_tpu.parallel.mesh import DATA_AXIS
        from d4pg_tpu.replay.device_per import min_tree_nodes

        self.mesh = mesh
        self.n_shards = int(mesh.shape[DATA_AXIS])
        # per-shard capacity, power of two for the tree layout
        self.cap_shard = next_pow2(
            max(1, int(np.ceil(capacity / self.n_shards))))
        self.capacity = self.cap_shard * self.n_shards
        obs_shape = (obs_dim,) if np.isscalar(obs_dim) else tuple(obs_dim)
        if obs_dtype is None:
            obs_dtype = np.float32 if len(obs_shape) == 1 else np.uint8
        self.prioritized = bool(prioritized)
        self.alpha = float(alpha)

        # multi-host: this process's contiguous block of data-axis shards
        # (contiguity is what make_array_from_process_local_data assembles
        # from; global_mesh()'s process-contiguous device order guarantees
        # it, and anything else is rejected here instead of mis-assembling)
        self._owned, self._multiproc = _owned_data_rows(mesh)
        self.n_local = len(self._owned)
        if self._multiproc:
            if not self.n_local:
                raise ValueError(
                    "this process owns no data-axis shard of the replay "
                    "mesh; every participating host needs local devices "
                    "on the data axis")
            if self._owned != list(range(self._owned[0],
                                         self._owned[0] + self.n_local)):
                raise ValueError(
                    f"this process's data-axis shards {self._owned} are "
                    "not contiguous; build the mesh with global_mesh() "
                    "(process-contiguous device order)")
        self.local_start = self._owned[0] if self._owned else 0

        shard = partition.batch_sharding(mesh)
        n, c = self.n_shards, self.cap_shard

        def _zero_storage():
            return TransitionBatch(
                obs=jnp.zeros((n, c, *obs_shape), obs_dtype),
                action=jnp.zeros((n, c, act_dim), jnp.float32),
                reward=jnp.zeros((n, c), jnp.float32),
                next_obs=jnp.zeros((n, c, *obs_shape), obs_dtype),
                done=jnp.zeros((n, c), jnp.float32),
                discount=jnp.zeros((n, c), jnp.float32),
            )

        def _zero_trees():
            return ShardedPerTrees(
                sum_tree=jnp.zeros((n, 2 * c), jnp.float32),
                min_tree=jnp.full((n, min_tree_nodes(c)), jnp.inf,
                                  jnp.float32),
                max_priority=jnp.ones((n,), jnp.float32),
            )

        # Constructed inside jit with sharded outputs, so every device
        # allocates only ITS shard: building the zeros eagerly and
        # device_put-ing them would materialise the whole ring on device 0
        # first (3 GB at 1M Humanoid rows), and a host-local device_put
        # cannot address other hosts' devices at all. One-shot by design
        # (runs once in __init__; SPMD — every process traces the same
        # zeros).
        self.storage = jax.jit(_zero_storage, out_shardings=shard)()  # jaxlint: disable=recompile-hazard
        self.trees = (jax.jit(_zero_trees, out_shardings=shard)()  # jaxlint: disable=recompile-hazard
                      if prioritized else None)
        # ring cursors / live sizes for the OWNED shards (host ints; the
        # device twin of sizes is the chunk's [n_shards] ``size`` operand)
        self._head = np.zeros(self.n_local, np.int64)
        self._size = np.zeros(self.n_local, np.int64)
        self._size_global = None  # cached global [n_shards] device array
        # round-robin cursor: which LOCAL shard receives the next staged row
        self._rr = 0
        self._staged: list[TransitionBatch] = []
        self._staged_rows = 0
        self._insert_fn = None

    @property
    def local_capacity(self) -> int:
        """Rows this host's shard-set can hold (== capacity single-host)."""
        return self.cap_shard * self.n_local

    # -- ingest side (drain thread, under the service's buffer lock) -------
    def add(self, batch: TransitionBatch) -> None:
        """Stage host rows; bounded at ~local capacity like the
        single-device fused buffer (oldest staged dropped — the next drain
        would overwrite them anyway)."""
        nrows = batch.obs.shape[0]
        if nrows == 0:
            return
        if nrows > self.local_capacity:
            raise ValueError(
                f"batch of {nrows} exceeds capacity {self.local_capacity}")
        self._staged.append(
            TransitionBatch(*[np.asarray(v) for v in batch]))
        self._staged_rows += nrows
        while (self._staged_rows - self._staged[0].obs.shape[0]
               >= self.local_capacity):
            self._staged_rows -= self._staged.pop(0).obs.shape[0]

    def __len__(self) -> int:
        """THIS host's row count (live + staged) — the per-host warmup
        gate; the global count is the sum over hosts."""
        return int(min(self._size.sum() + self._staged_rows,
                       self.local_capacity))

    @property
    def size(self):
        """Per-shard live sizes [n_shards] (the chunk's ``size`` operand).
        Multi-host: a globally-sharded device array assembled from each
        host's local sizes (cached until the next drain/restore)."""
        if not self._multiproc:
            return self._size.astype(np.int32)
        if self._size_global is None:
            import jax

            from d4pg_tpu.parallel import partition

            self._size_global = jax.make_array_from_process_local_data(
                partition.batch_sharding(self.mesh),
                self._size.astype(np.int32), (self.n_shards,))
        return self._size_global

    # -- learner side ------------------------------------------------------
    def _make_insert(self):
        """shard_map'd insert: each device scatters its rows into its ring
        shard and stamps ``max_priority ** alpha`` into its trees. Pad
        rows carry local idx == cap_shard, which both the ring scatter
        (``mode='drop'``) and the tree write (``set_leaves``'s pad-drop
        convention) discard."""
        import jax
        from jax import shard_map

        from d4pg_tpu.parallel import partition
        from d4pg_tpu.replay import device_per as dper

        alpha = self.alpha

        def local_insert(storage, trees, idx, rows):
            # locals: storage [1, c, ...], trees [1, ...], idx [1, m],
            # rows [1, m, ...]; pad entries carry idx == cap_shard and are
            # dropped by both the ring scatter and the tree write
            new_storage = TransitionBatch(*[
                arr.at[0, idx[0]].set(v[0].astype(arr.dtype), mode="drop")
                for arr, v in zip(storage, rows)
            ])
            if trees is None:
                return new_storage, None
            t = dper.PerTrees(trees.sum_tree[0], trees.min_tree[0],
                              trees.max_priority[0])
            t = dper.insert(t, idx[0], alpha)
            return new_storage, ShardedPerTrees(
                t.sum_tree[None], t.min_tree[None], t.max_priority[None])

        specs = partition.data_spec()
        if self.trees is not None:
            fn = shard_map(
                local_insert, mesh=self.mesh,
                in_specs=(specs, specs, specs, specs),
                out_specs=(specs, specs), check_vma=False)
            return jax.jit(fn, donate_argnums=(0, 1))
        fn2 = shard_map(
            lambda s, i, r: local_insert(s, None, i, r)[0],
            mesh=self.mesh, in_specs=(specs, specs, specs),
            out_specs=specs, check_vma=False)
        return jax.jit(fn2, donate_argnums=(0,))

    def drain(self) -> int:
        """Flush staged rows round-robin across this host's shards.
        Learner thread only (single owner of the donated handles).

        MULTI-HOST: a COLLECTIVE call — every host must reach it at the
        same point (train.py's chunk boundaries are lockstep). One scalar
        allgather agrees on the pad width so all hosts execute the same
        SPMD insert; a host with nothing staged contributes all-pad rows.
        """
        if not self._staged and not self._multiproc:
            return 0
        if self._staged:
            batch = (self._staged[0] if len(self._staged) == 1 else
                     TransitionBatch(*[
                         np.concatenate(
                             [np.asarray(b[f]) for b in self._staged])
                         for f in range(len(self._staged[0]))]))
            nrows = batch.obs.shape[0]
        else:
            batch, nrows = None, 0
        self._staged.clear()
        self._staged_rows = 0
        if nrows > self.local_capacity:
            # keep exactly the newest rows that fit: a larger backlog
            # would hand some shard more than cap_shard rows, i.e.
            # duplicate slots in one scatter (unspecified winner)
            batch = TransitionBatch(
                *[v[-self.local_capacity:] for v in batch])
            nrows = self.local_capacity
        n, cap = self.n_local, self.cap_shard

        # pad width m: power of two for the jit cache; multi-host takes
        # the max over hosts so every process runs the same program
        m = next_pow2(int(np.ceil(nrows / n))) if nrows else 0
        if self._multiproc:
            from jax.experimental import multihost_utils

            m = int(np.max(multihost_utils.process_allgather(
                np.int64(m))))
        if m == 0:
            return 0

        # round-robin shard assignment, then per-shard local slots; with
        # nothing staged locally (multi-host, a peer had rows) the arrays
        # stay all-pad — shapes/dtypes come from the ring itself
        local_idx = np.full((n, m), cap, np.int32)  # cap -> dropped pad
        rows = TransitionBatch(*[
            np.zeros((n, m, *arr.shape[2:]), arr.dtype)
            for arr in self.storage
        ])
        if nrows:
            shard_of = (self._rr + np.arange(nrows)) % n
            self._rr = int((self._rr + nrows) % n)
            for s in range(n):
                take = np.flatnonzero(shard_of == s)
                cnt = len(take)
                if cnt == 0:
                    continue
                local_idx[s, :cnt] = (self._head[s] + np.arange(cnt)) % cap
                for f in range(len(rows)):
                    rows[f][s, :cnt] = np.asarray(batch[f])[take]
                self._head[s] = int((self._head[s] + cnt) % cap)
                self._size[s] = int(min(self._size[s] + cnt, cap))
            self._size_global = None

        if self._multiproc:
            local_idx, rows = self._assemble_global(local_idx, rows)
        if self._insert_fn is None:
            self._insert_fn = self._make_insert()
        if self.trees is not None:
            self.storage, self.trees = self._insert_fn(
                self.storage, self.trees, local_idx, rows)
        else:
            self.storage = self._insert_fn(self.storage, local_idx, rows)
        return nrows

    def _assemble_global(self, local_idx, rows):
        """Lift this host's [n_local, m, ...] staging arrays to global
        [n_shards, m, ...] arrays sharded over the data axis (each process
        contributes its own block; nothing crosses DCN)."""
        import jax

        from d4pg_tpu.parallel import partition

        shard = partition.batch_sharding(self.mesh)

        def to_global(x):
            x = np.asarray(x)
            return jax.make_array_from_process_local_data(
                shard, x, (self.n_shards, *x.shape[1:]))

        return to_global(local_idx), TransitionBatch(
            *[to_global(v) for v in rows])

    # -- checkpointing -----------------------------------------------------
    def _local_block(self, arr, axis: int = 0):
        """This host's contiguous block of a data-axis-sharded array as
        host numpy (dedups model-axis replicas by shard start index)."""
        seen = {}
        for s in arr.addressable_shards:
            start = s.index[axis].start or 0
            if start not in seen:
                seen[start] = np.asarray(s.data)
        return np.concatenate([seen[k] for k in sorted(seen)], axis=axis)

    def state_dict(self) -> dict:
        """Checkpoint payload for THIS host's shard-set. Single-host that
        is the whole buffer; multi-host each host snapshots only its own
        shards (the per-host sidecar scheme in ``train.py``) — collective
        (the leading drain), so all hosts must checkpoint in lockstep."""
        self.drain()
        host = TransitionBatch(
            *[self._local_block(v) for v in self.storage])
        d = pack_rows(host, 0, 0, self.capacity)
        d["sharded"] = {
            "head": self._head.copy(),
            "size": self._size.copy(),
            "rr": self._rr,
            "n_shards": self.n_shards,
            "n_local": self.n_local,
            "local_start": self.local_start,
        }
        if self.trees is not None:
            d["sharded"]["leaf_priorities"] = self._local_block(
                self.trees.sum_tree)[:, self.cap_shard:]
            d["sharded"]["max_priority"] = self._local_block(
                self.trees.max_priority)
        return d

    def load_state_dict(self, d: dict) -> None:
        """Restore this host's shard-set. Multi-host: collective — every
        host loads ITS OWN snapshot at the same point (train.py agrees on
        snapshot availability across hosts before any host calls this)."""
        import jax
        import jax.numpy as jnp

        from d4pg_tpu.parallel import partition

        s = d.get("sharded")
        if s is None:
            raise ValueError(
                "replay checkpoint was saved by a non-sharded buffer; "
                "resume with the same replay layout (data_parallel=1 or "
                "host storage)")
        if int(s["n_shards"]) != self.n_shards:
            raise ValueError(
                "sharded replay checkpoint requires the same data-parallel "
                f"degree (got {s['n_shards']}, have {self.n_shards})")
        n_local = int(s.get("n_local", s["n_shards"]))
        start = int(s.get("local_start", 0))
        if n_local != self.n_local or start != self.local_start:
            raise ValueError(
                f"replay snapshot covers shards [{start}, {start + n_local})"
                f" but this host owns [{self.local_start}, "
                f"{self.local_start + self.n_local}); resume with the same "
                "host topology (process count and devices per host)")
        validate_rows({k: v for k, v in d.items() if k != "sharded"},
                      self.capacity)
        shard = partition.batch_sharding(self.mesh)
        n, c = self.n_local, self.cap_shard

        def to_global(x):
            x = np.asarray(x)
            if not self._multiproc:
                return jax.device_put(jnp.asarray(x), shard)
            return jax.make_array_from_process_local_data(
                shard, x, (self.n_shards, *x.shape[1:]))

        self.storage = TransitionBatch(
            *[to_global(d["rows"][f]) for f in TransitionBatch._fields])
        self._head = np.asarray(s["head"]).astype(np.int64).copy()
        self._size = np.asarray(s["size"]).astype(np.int64).copy()
        self._size_global = None
        self._rr = int(s["rr"])
        if self.trees is not None:
            from d4pg_tpu.replay.device_per import (min_tree_nodes,
                                                    repair_plan)

            leaves = np.asarray(s["leaf_priorities"], np.float32)
            sum_tree = np.zeros((n, 2 * c), np.float32)
            min_tree = np.full((n, min_tree_nodes(c)), np.inf, np.float32)
            for sh in range(n):
                sz = int(self._size[sh])
                sum_tree[sh, c:c + sz] = leaves[sh, :sz]
            # rebuild the kept levels as device_per.set_leaves leaves them
            # (its invariant: rounds of adjacent pairs from the kept level
            # below, float32; the min tree has no leaves and reads the sum
            # tree's, an empty slot at inf; no other node is written),
            # vectorized across shards, so a restored tree equals a live
            # one array for array
            lvl_s = sum_tree[:, c:]
            lvl_m = np.where(lvl_s > 0, lvl_s, np.float32(np.inf))
            if c == 1:  # one leaf: it is the root
                min_tree[:, 1:] = lvl_m
            for below, above, _form in repair_plan(c, c):
                for _ in range(below - above):
                    lvl_s = lvl_s[:, 0::2] + lvl_s[:, 1::2]
                    lvl_m = np.minimum(lvl_m[:, 0::2], lvl_m[:, 1::2])
                sum_tree[:, 1 << above:2 << above] = lvl_s
                min_tree[:, 1 << above:2 << above] = lvl_m
            self.trees = ShardedPerTrees(
                sum_tree=to_global(sum_tree),
                min_tree=to_global(min_tree),
                max_priority=to_global(
                    np.asarray(s["max_priority"], np.float32)),
            )
