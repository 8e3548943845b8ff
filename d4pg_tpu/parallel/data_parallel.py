"""Placing a learner's state and batches on the device mesh, and what a
mesh learner refuses.

The data-parallel learner itself is the one set of builders in
``learner/update.py`` and ``learner/fused.py`` called with ``mesh=``: the
train state carries rule-resolved shardings (replicated except where the
partition table says otherwise — the pixel encoder's ``model``-axis
tenancy), the batch is sharded over the ``data`` axis, and the SAME
``update_step`` used single-chip is jit'd with those shardings. That
replaces the reference's distributed-update machinery — grad aliasing into
shared tensors (``ddpg.py:104-108``), racy ``SharedAdam.step()`` from N
processes (``shared_adam.py``), weight pull-back (``ddpg.py:118-120``) and
the 1/n_workers lr rescale (``main.py:384-385``) — by a synchronous,
deterministic update, race-free by construction (SURVEY.md §5).

Every sharding here comes from ``parallel/partition.py`` — the single
source of sharding truth (jaxlint ``sharding-rule-bypass`` enforces it).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

from d4pg_tpu.learner.state import D4PGConfig, D4PGState
from d4pg_tpu.replay.uniform import TransitionBatch

from d4pg_tpu.parallel import partition

# Re-exported for the training loop's chunk staging (the [K, B, ...]
# layout helper used to live here; partition.py owns it now).
stacked_sharding = partition.stacked_sharding


def replicate_state(state: D4PGState, mesh: Mesh) -> D4PGState:
    """Place the train state over the mesh by partition rule — fully
    replicated for MLP configs; pixel configs put the conv encoder's
    kernels/biases on the ``model`` axis (``partition.D4PG_RULES``)."""
    return jax.device_put(state, partition.shardings_for(mesh, state))


def shard_batch(batch: TransitionBatch, mesh: Mesh) -> TransitionBatch:
    """Shard a host batch over the ``data`` axis (leading dim split across
    the mesh's data dimension). The batch size must divide evenly."""
    return jax.device_put(batch, partition.batch_sharding(mesh))


def shard_stacked(batches, mesh: Mesh):
    """Shard a [K, B, ...] stack of batches: the scan axis K stays
    replicated, B splits over ``data``. Works on any pytree whose leaves
    carry the [K, B, ...] layout (TransitionBatch stacks, weight stacks)."""
    return jax.device_put(batches, partition.stacked_sharding(mesh))


def check_mesh_compatible(config: D4PGConfig) -> None:
    """What a mesh learner cannot run: refused loudly, before anything
    compiles."""
    if config.torso is not None:
        raise ValueError(
            "a torso (--torso) runs on one device: its expert layer is one "
            "chip's share without the exchange, and its metrics "
            "(route_counts) and kernels have no sharding rule yet "
            "(ROADMAP Reach 10)")
