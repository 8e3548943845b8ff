"""Fully-fused replay+learn chunk: K grad steps in ONE device dispatch.

The hot-loop endgame of the TPU redesign. The reference's per-step
protocol (``ddpg.py:200-255``) is sample -> nets -> projection -> Adam ->
priority write-back, with the replay machinery on the host. The
host-pipelined chunk path (``learner/pipeline.py``) already overlaps host
sampling with device compute, but still pays per-chunk dispatches and a
blocking host<->device sync per chunk: costs that do not shrink with the
model, while a step of the small MLPs is 139.5 us on the device, 38.8 of
them the update and the rest the replay protocol (ledger, PR 43, cell 1).

With the transition ring (``replay/device_ring.py``) AND the PER trees
(``replay/device_per.py``) resident in HBM, the whole protocol becomes
pure jnp inside one ``lax.scan`` (``fused_chunk_step``, the one body):

    per step: stratified PER sample -> ring gather -> IS weights ->
              D4PG update -> priority write-back

so one dispatch carries K full steps with ZERO host round trips and ZERO
priority staleness (fresher than the reference: within a chunk, step
t+1's sampling distribution already reflects step t's TD errors — the
host-pipelined path bounds staleness at (depth+1)K instead). The host's only
jobs left are draining actor transitions into the ring between chunks
and fetching metrics when it wants them. What differs between one device
and a mesh is how a step samples and writes back (``device_replay``,
``mesh_replay``); ``make_fused_chunk`` picks by its ``mesh`` argument.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from d4pg_tpu.learner.state import D4PGConfig, D4PGState
from d4pg_tpu.learner.update import mesh_shardings, update_step
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch


def fused_chunk_step(
    config: D4PGConfig,
    state: D4PGState,
    trees,
    storage: TransitionBatch,
    size,
    *,
    k: int,
    sample,
    write_back,
):
    """K fused sample+update steps: the one scan body of every fused
    chunk. Pure; jit via :func:`make_fused_chunk`.

    ``trees=None`` is uniform replay (no IS weights, no write-back).
    ``storage`` is the device ring's arrays; ``size`` the live row count
    (traced int32). What differs between one device and a mesh is handed
    in (:func:`device_replay`, :func:`mesh_replay`): ``sample(trees,
    storage, size, key, step) -> (batch, weights, idx)`` and
    ``write_back(trees, idx, td_error) -> trees``.

    Returns ``(state, trees, metrics)`` with per-step metrics stacked [K]
    (plus ``td_error``/``idx`` [K, B] for observability and the priority
    tests).
    """

    def body(carry, _):
        state, trees = carry
        k_sample, k_rest = jax.random.split(state.key)
        state = state._replace(key=k_rest)
        # The four phases carry named scopes (metadata only; replay.sample
        # and replay.gather inside ``sample``): a trace reader turns each
        # into device time through the program table
        # (obs/trace.compiled_text); PERF.md section 3 names the metrics.
        batch, w, idx = sample(trees, storage, size, k_sample, state.step)
        with jax.named_scope("learner.update"):
            state, metrics = update_step(config, state, batch, w)
        if trees is not None:
            with jax.named_scope("replay.writeback"):
                trees = write_back(trees, idx, metrics["td_error"])
        metrics["idx"] = idx
        return (state, trees), metrics

    (state, trees), metrics = jax.lax.scan(
        body, (state, trees), None, length=k)
    return state, trees, metrics


def device_replay(batch_size: int, alpha: float, beta0: float,
                  beta_steps: int):
    """``(sample, write_back)`` of :func:`fused_chunk_step` against one
    device's ring and trees (``replay/device_per.py``): stratified PER
    sample -> IS weights -> ring gather, or ``randint`` rows without
    trees; TD errors written back into the trees."""

    def sample(trees, storage, size, key, step):
        with jax.named_scope("replay.sample"):
            if trees is not None:
                # the two halves of the phase, for whoever reads a trace
                # by hand (PERF.md section 5); no metric reads them
                with jax.named_scope("sample.descend"):
                    idx = dper.sample(trees, key, batch_size, size)
                with jax.named_scope("sample.weights"):
                    beta = dper.beta_schedule(step, beta0, beta_steps)
                    w = dper.is_weights(trees, idx, beta, size)
            else:
                idx = jax.random.randint(key, (batch_size,), 0,
                                         jnp.maximum(size, 1))
                w = None
        with jax.named_scope("replay.gather"):
            batch = TransitionBatch(*[arr[idx] for arr in storage])
        return batch, w, idx

    def write_back(trees, idx, td):
        return dper.update_from_td(trees, idx, td, alpha)

    return sample, write_back


def mesh_replay(mesh, batch_size: int, alpha: float, beta0: float,
                beta_steps: int):
    """``(sample, write_back)`` of :func:`fused_chunk_step` with the replay
    data plane ON a data-parallel mesh.

    Storage/trees come from ``replay/sharded_per.ShardedFusedReplay``
    (leading axis = shard, sharded over ``data``; ``size`` the per-shard
    live-row count [n_shards]). Per step, a ``shard_map`` prologue lets
    every device sample B/N rows from ITS ring shard (stratified across
    shards by construction) and compute IS weights with a GLOBAL
    max-weight normalizer (``lax.pmin`` over the data axis — per-shard
    normalizers would bias gradient scale, the same correction the
    multi-host host-tree path makes with its allgather). The batch emerges
    already sharded ``P('data')``, so under GSPMD the ordinary
    ``update_step``'s loss mean turns into the usual ICI all-reduce. A
    second ``shard_map`` writes each shard's TD errors back into its own
    trees. Batch rows never cross devices; only gradients do.
    """
    from jax import shard_map

    from d4pg_tpu.parallel import partition
    from d4pg_tpu.parallel.mesh import DATA_AXIS
    from d4pg_tpu.replay.sharded_per import ShardedPerTrees

    n_shards = int(mesh.shape[DATA_AXIS])
    if batch_size % n_shards:
        raise ValueError(
            f"batch_size {batch_size} not divisible by data axis {n_shards}")
    b_local = batch_size // n_shards
    Pd, Pr = partition.data_spec(), partition.replicated_spec()

    def _local_trees(trees):
        return dper.PerTrees(trees.sum_tree[0], trees.min_tree[0],
                             trees.max_priority[0])

    def _local_sample_per(trees, storage, size, key, beta):
        ax = jax.lax.axis_index(DATA_AXIS)
        t = _local_trees(trees)
        with jax.named_scope("replay.sample"), \
                jax.named_scope("sample.descend"):
            idx = dper.sample(t, jax.random.fold_in(key, ax), b_local,
                              size[0])
        with jax.named_scope("replay.gather"):
            batch = TransitionBatch(*[arr[0][idx] for arr in storage])
        # per-draw probability of row i: q_i = (1/N_shards) * p_i/total_h.
        # The reference weight is (N_rows * q)^-beta / (N_rows * q_min)^-beta
        # — N_rows cancels, so no psum of sizes is needed; only the global
        # minimum per-draw probability crosses shards (one pmin scalar).
        with jax.named_scope("replay.sample"), \
                jax.named_scope("sample.weights"):
            total = jnp.maximum(t.sum_tree[1], 1e-30)
            q = t.sum_tree[t.capacity + idx] / total / n_shards
            q_min = jax.lax.pmin(t.min_tree[1] / total / n_shards,
                                 DATA_AXIS)
            w = (q / q_min) ** (-beta)
        return batch, w.astype(jnp.float32), idx.astype(jnp.int32)

    def _local_sample_uniform(storage, size, key):
        ax = jax.lax.axis_index(DATA_AXIS)
        with jax.named_scope("replay.sample"):
            idx = jax.random.randint(
                jax.random.fold_in(key, ax), (b_local,), 0,
                jnp.maximum(size[0], 1))
        with jax.named_scope("replay.gather"):
            batch = TransitionBatch(*[arr[0][idx] for arr in storage])
        return batch, idx.astype(jnp.int32)

    def _local_write_back(trees, idx, td):
        t = dper.update_from_td(_local_trees(trees), idx, td, alpha)
        return ShardedPerTrees(t.sum_tree[None], t.min_tree[None],
                               t.max_priority[None])

    sample_per = shard_map(
        _local_sample_per, mesh=mesh,
        in_specs=(Pd, Pd, Pd, Pr, Pr), out_specs=(Pd, Pd, Pd),
        check_vma=False)
    sample_uniform = shard_map(
        _local_sample_uniform, mesh=mesh,
        in_specs=(Pd, Pd, Pr), out_specs=(Pd, Pd), check_vma=False)
    write_back = shard_map(
        _local_write_back, mesh=mesh,
        in_specs=(Pd, Pd, Pd), out_specs=Pd, check_vma=False)

    def sample(trees, storage, size, key, step):
        # the prologue samples and gathers in one call, so those two
        # scopes sit inside its local functions
        if trees is None:
            batch, idx = sample_uniform(storage, size, key)
            return batch, None, idx
        with jax.named_scope("replay.sample"), \
                jax.named_scope("sample.weights"):
            beta = dper.beta_schedule(step, beta0, beta_steps)
        return sample_per(trees, storage, size, key, beta)

    return sample, write_back


def make_fused_chunk(
    config: D4PGConfig,
    *,
    k: int,
    batch_size: int,
    alpha: float = 0.6,
    beta0: float = 0.4,
    beta_steps: int = 100_000,
    mesh=None,
    donate: bool = True,
):
    """jit the fused chunk: ``fn(state, trees, storage, size) -> (state,
    trees, metrics)``, ``trees=None`` for uniform replay (an empty pytree:
    no operand, no result). ``state`` and ``trees`` are donated (updated in
    place in HBM). The ring is read-only and taken in the formats the
    store keeps it in (``replay/device_ring.py``, "Layout"): the gather
    reads B rows from the parameter itself, float rows and ``uint8``
    frames alike, and only those rows are ever cast
    (``core/precision.to_compute`` at the models' inputs). Until PR 31
    (frames: PR 33) "never copied" was false on the chip: a ring with its
    rows on the lanes was re-laid whole, once a dispatch.

    With a ``mesh`` the chunk runs data-parallel over it against
    :func:`mesh_replay`'s sharded ring and trees."""
    over = {}
    if mesh is None:
        sample, write_back = device_replay(batch_size, alpha, beta0,
                                           beta_steps)
    else:
        from d4pg_tpu.parallel import partition

        shard = partition.batch_sharding(mesh)
        stacked = partition.stacked_sharding(mesh)
        state_sh, metrics_sh = mesh_shardings(config, mesh, td_error=stacked)
        over = dict(
            in_shardings=(state_sh, shard, shard, shard),
            out_shardings=(state_sh, shard, {**metrics_sh, "idx": stacked}))
        sample, write_back = mesh_replay(mesh, batch_size, alpha, beta0,
                                         beta_steps)

    def fn(state, trees, storage, size):
        return fused_chunk_step(config, state, trees, storage, size, k=k,
                                sample=sample, write_back=write_back)

    return jax.jit(fn, donate_argnums=(0, 1) if donate else (), **over)
