"""Synchronous data-parallel learner over the device mesh.

Replaces the reference's entire distributed-update machinery — grad aliasing
into shared tensors (``ddpg.py:104-108``), racy ``SharedAdam.step()`` from N
processes (``shared_adam.py``), weight pull-back (``ddpg.py:118-120``) and
the 1/n_workers lr rescale (``main.py:384-385``) — with the GSPMD
formulation: the train state carries rule-resolved shardings (replicated
except where the partition table says otherwise — the pixel encoder's
``model``-axis tenancy), the batch is sharded over the ``data`` axis, and
the SAME ``update_step`` used single-chip is jit'd with those shardings.
``jnp.mean`` over the global batch inside the loss becomes an XLA
all-reduce over ICI; every replica then applies an identical Adam update —
synchronous, deterministic, race-free by construction (SURVEY.md §5).

Every sharding here comes from ``parallel/partition.py`` — the single
source of sharding truth (jaxlint ``sharding-rule-bypass`` enforces it).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

from d4pg_tpu.learner.state import D4PGConfig, D4PGState
from d4pg_tpu.learner.update import multi_update_step, update_step
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
            "(ROADMAP Reach 11)")


def make_sharded_update(
    config: D4PGConfig,
    mesh: Mesh,
    donate: bool = True,
    use_is_weights: bool = True,
):
    """jit the D4PG update with explicit shardings over ``mesh``.

    in: state by partition rule, batch + IS weights sharded over
    ``data``. out: state by the same rules, scalar metrics replicated,
    per-sample ``td_error`` sharded over ``data`` (it flows back to the
    host PER priority update, ``ddpg.py:252-255``).
    """
    check_mesh_compatible(config)
    repl = partition.replicated(mesh)
    shard = partition.batch_sharding(mesh)
    state_sh = partition.state_shardings(config, mesh)

    # Shardings as pytree prefixes: a single sharding broadcasts to the
    # tree; the state's is a full rule-resolved tree.
    in_shardings: tuple
    out_metrics = {
        "critic_loss": repl,
        "actor_loss": repl,
        "q_mean": repl,
        "td_error": shard,
    }
    if use_is_weights:
        fn = lambda state, batch, w: update_step(config, state, batch, w)
        in_shardings = (state_sh, shard, shard)
    else:
        fn = lambda state, batch: update_step(config, state, batch, None)
        in_shardings = (state_sh, shard)
    return jax.jit(
        fn,
        in_shardings=in_shardings,
        out_shardings=(state_sh, out_metrics),
        donate_argnums=(0,) if donate else (),
    )


def make_sharded_multi_update(
    config: D4PGConfig,
    mesh: Mesh,
    donate: bool = True,
    use_is_weights: bool = True,
):
    """jit the K-step scanned update with explicit shardings over ``mesh`` —
    the production configuration: dispatch amortization (K ``lax.scan``
    steps per device round trip) COMBINED with data parallelism (each step's
    [B, ...] batch split over the ``data`` axis, gradients all-reduced by
    XLA-inserted collectives over ICI).

    in: state by partition rule, batches [K, B, ...] + weights [K, B]
    sharded ``stacked_spec()``. out: state by the same rules, scalar
    metrics stacked [K] replicated, ``td_error`` [K, B] sharded like the
    batches.
    """
    check_mesh_compatible(config)
    repl = partition.replicated(mesh)
    stacked = partition.stacked_sharding(mesh)
    state_sh = partition.state_shardings(config, mesh)
    out_metrics = {
        "critic_loss": repl,
        "actor_loss": repl,
        "q_mean": repl,
        "td_error": stacked,
    }
    if use_is_weights:
        fn = lambda state, batches, w: multi_update_step(config, state, batches, w)
        in_shardings: tuple = (state_sh, stacked, stacked)
    else:
        fn = lambda state, batches: multi_update_step(config, state, batches)
        in_shardings = (state_sh, stacked)
    return jax.jit(
        fn,
        in_shardings=in_shardings,
        out_shardings=(state_sh, out_metrics),
        donate_argnums=(0,) if donate else (),
    )
