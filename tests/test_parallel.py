"""Sharded data-parallel learner tests on the 8-virtual-device CPU mesh
(SURVEY.md §4: multi-host behavior simulated with 8 local XLA CPU devices).

The key property: the sharded update is EQUIVALENT to the single-device
update on the same global batch — the synchronous replacement for the
reference's racy hogwild scheme has no semantic drift, only layout.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.learner import D4PGConfig, init_state, make_update
from d4pg_tpu.parallel import (
    MeshSpec,
    make_mesh,
    replicate_state,
    shard_batch,
)
from d4pg_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from d4pg_tpu.replay.uniform import TransitionBatch

OBS, ACT, B = 4, 2, 64


def _config(**kw):
    base = dict(obs_dim=OBS, act_dim=ACT, v_min=-5.0, v_max=5.0, n_atoms=11,
                hidden=(32, 32, 32))
    base.update(kw)
    return D4PGConfig(**base)


def _batch(rng):
    done = (rng.random(B) < 0.2).astype(np.float32)
    return TransitionBatch(
        obs=rng.standard_normal((B, OBS)).astype(np.float32),
        action=rng.uniform(-1, 1, (B, ACT)).astype(np.float32),
        reward=rng.standard_normal(B).astype(np.float32),
        next_obs=rng.standard_normal((B, OBS)).astype(np.float32),
        done=done,
        discount=(0.99 * (1.0 - done)).astype(np.float32),
    )


def test_mesh_geometry():
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    mesh = make_mesh(MeshSpec())
    assert mesh.shape[DATA_AXIS] == 8 and mesh.shape[MODEL_AXIS] == 1
    mesh2 = make_mesh(MeshSpec(data_parallel=4, model_parallel=2))
    assert mesh2.shape[DATA_AXIS] == 4 and mesh2.shape[MODEL_AXIS] == 2
    with pytest.raises(ValueError):
        make_mesh(MeshSpec(data_parallel=3))


def test_batch_sharded_state_replicated(rng):
    config = _config()
    mesh = make_mesh()
    state = replicate_state(init_state(config, jax.random.key(0)), mesh)
    batch = shard_batch(_batch(rng), mesh)
    # batch leading dim split 8 ways; params present on all devices
    assert len(batch.obs.sharding.device_set) == 8
    leaf = jax.tree_util.tree_leaves(state.actor_params)[0]
    assert leaf.sharding.is_fully_replicated


def test_sharded_update_matches_single_device(rng):
    """Bitwise-level equivalence (up to float tolerance) between the sharded
    and single-device update on the same global batch."""
    config = _config()
    batch = _batch(rng)
    w = np.ones((B,), np.float32)

    ref_state = init_state(config, jax.random.key(42))
    ref_update = make_update(config, donate=False)
    ref_next, ref_metrics = ref_update(ref_state, batch, jnp.asarray(w))

    mesh = make_mesh()
    sh_state = replicate_state(init_state(config, jax.random.key(42)), mesh)
    sh_update = make_update(config, mesh=mesh, donate=False)
    sh_next, sh_metrics = sh_update(sh_state, shard_batch(batch, mesh),
                                    shard_batch(jnp.asarray(w), mesh))

    np.testing.assert_allclose(
        float(ref_metrics["critic_loss"]), float(sh_metrics["critic_loss"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(ref_metrics["td_error"]), np.asarray(sh_metrics["td_error"]),
        rtol=1e-4, atol=1e-5,
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(ref_next.critic_params),
        jax.tree_util.tree_leaves(sh_next.critic_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_sharded_update_multi_step_stability(rng):
    """Several sharded steps run and keep params replicated + finite."""
    config = _config()
    mesh = make_mesh()
    state = replicate_state(init_state(config, jax.random.key(1)), mesh)
    update = make_update(config, mesh=mesh, donate=False)
    for _ in range(3):
        state, metrics = update(state, shard_batch(_batch(rng), mesh), None)
    leaf = jax.tree_util.tree_leaves(state.actor_params)[0]
    assert leaf.sharding.is_fully_replicated
    assert np.isfinite(float(metrics["critic_loss"]))
    assert int(state.step) == 3


def test_sharded_multi_update_matches_sequential(rng):
    """The production config (VERDICT r1 #3): K scanned updates sharded over
    the data axis == K sequential sharded updates on the same batches."""
    from d4pg_tpu.learner import make_multi_update
    from d4pg_tpu.parallel import shard_stacked

    config = _config()
    K = 4
    batches = [_batch(rng) for _ in range(K)]
    w = np.ones((B,), np.float32)

    mesh = make_mesh(MeshSpec(data_parallel=4), devices=jax.devices()[:4])
    seq_state = replicate_state(init_state(config, jax.random.key(7)), mesh)
    seq_update = make_update(config, mesh=mesh, donate=False)
    seq_tds = []
    for b in batches:
        seq_state, m = seq_update(seq_state, shard_batch(b, mesh),
                                  shard_batch(jnp.asarray(w), mesh))
        seq_tds.append(np.asarray(m["td_error"]))

    stacked = TransitionBatch(*[np.stack(x) for x in zip(*batches)])
    multi_state = replicate_state(init_state(config, jax.random.key(7)), mesh)
    multi_update = make_multi_update(config, mesh=mesh, donate=False)
    multi_state, ms = multi_update(
        multi_state,
        shard_stacked(stacked, mesh),
        shard_stacked(jnp.ones((K, B), jnp.float32), mesh),
    )

    assert int(jax.device_get(multi_state.step)) == K
    np.testing.assert_allclose(
        np.asarray(ms["td_error"]), np.stack(seq_tds), rtol=1e-4, atol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(seq_state.critic_params),
        jax.tree_util.tree_leaves(multi_state.critic_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    leaf = jax.tree_util.tree_leaves(multi_state.actor_params)[0]
    assert leaf.sharding.is_fully_replicated


def test_train_mesh_with_updates_per_dispatch(tmp_path):
    """End-to-end train() on a 2-device data mesh WITH K>1 fused dispatch —
    the round-1 degrade path is gone (VERDICT r1 #3)."""
    from d4pg_tpu.config import ExperimentConfig
    from d4pg_tpu.train import train

    cfg = ExperimentConfig(
        env="point", max_steps=20, num_envs=2, warmup=100, n_epochs=1,
        n_cycles=2, episodes_per_cycle=1, train_steps_per_cycle=5,
        eval_trials=1, batch_size=16, memory_size=2000,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-5.0, v_max=0.0, data_parallel=2, updates_per_dispatch=2,
    )
    metrics = train(cfg)
    assert np.isfinite(metrics["critic_loss"])
    assert "avg_test_reward" in metrics


def _tree_equal(got, want):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b).reshape(np.shape(a)),
            jax.tree_util.keystr(path))


@pytest.mark.parametrize(
    "program", ["update", "multi_update", "fused_uniform", "fused_per"])
def test_a_mesh_of_one_device_is_no_mesh_bit_for_bit(program, rng):
    """``mesh=`` is layout only: each of the three builders gives bitwise
    the same state and metrics over a mesh of one device as without one
    (PR 14 pinned this for ``mesh_replicas``). The fused chunk's mesh pair
    folds the shard's index into the sampling key, so its draws differ by
    design: its ring holds one row B times over, so that every draw is the
    same batch (PER: one step, before a write-back tells rows apart)."""
    from d4pg_tpu.learner import make_fused_chunk, make_multi_update
    from d4pg_tpu.parallel import shard_stacked
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay
    from d4pg_tpu.replay.sharded_per import ShardedFusedReplay

    config = _config()
    mesh = make_mesh(MeshSpec(data_parallel=1), devices=jax.devices()[:1])
    state = init_state(config, jax.random.key(11))
    on_mesh = replicate_state(state, mesh)
    batch, w = _batch(rng), rng.uniform(0.5, 1.0, B).astype(np.float32)
    if program == "update":
        want = make_update(config, donate=False)(state, batch, w)
        got = make_update(config, mesh=mesh, donate=False)(
            on_mesh, shard_batch(batch, mesh), shard_batch(w, mesh))
    elif program == "multi_update":
        stack = lambda x: np.stack([x, x[::-1]])  # noqa: E731
        batches = jax.tree_util.tree_map(stack, batch)
        want = make_multi_update(config, donate=False)(
            state, batches, stack(w))
        got = make_multi_update(config, mesh=mesh, donate=False)(
            on_mesh, shard_stacked(batches, mesh),
            shard_stacked(stack(w), mesh))
    else:
        per = program == "fused_per"
        rows = jax.tree_util.tree_map(lambda x: np.repeat(x[:1], B, 0),
                                      batch)
        kw = dict(k=1 if per else 3, batch_size=16, alpha=0.6, donate=False)
        one = FusedDeviceReplay(B, OBS, ACT, alpha=0.6, prioritized=per,
                                block_rows=B)
        many = ShardedFusedReplay(B, OBS, ACT, mesh, alpha=0.6,
                                  prioritized=per)
        for buf in (one, many):
            buf.add(rows)
            buf.drain()
        assert (one.trees is not None) == per == (many.trees is not None)
        want = make_fused_chunk(config, **kw)(
            state, one.trees, one.storage, one.size)
        got = make_fused_chunk(config, mesh=mesh, **kw)(
            on_mesh, many.trees, many.storage, many.size)
        for out in (want, got):
            out[2].pop("idx")  # which copies of the row: the draws differ
        if per:  # as many leaves moved, to the same priority, whichever
            leaves = [np.sort(np.asarray(t.sum_tree).reshape(-1)[B:])
                      for t in (want[1], got[1])]
            assert np.unique(leaves[0]).size == 2
            np.testing.assert_array_equal(leaves[1], leaves[0])
            want, got = want[::2], got[::2]
    _tree_equal(got, want)
