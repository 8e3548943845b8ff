"""Sharded device-resident replay over the data-parallel mesh
(replay/sharded_per.py + learner/fused.make_fused_chunk), on the
8-virtual-CPU-device mesh. The host segment trees serve as the oracle
for the per-shard tree state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.parallel import MeshSpec, make_mesh
from d4pg_tpu.replay.sharded_per import ShardedFusedReplay
from d4pg_tpu.replay.uniform import TransitionBatch


def _mesh(dp=4):
    return make_mesh(MeshSpec(data_parallel=dp),
                     devices=jax.devices()[:dp])


def _batch(rng, n, obs_dim=4, act_dim=2):
    done = np.zeros(n, np.float32)
    return TransitionBatch(
        obs=rng.standard_normal((n, obs_dim)).astype(np.float32),
        action=rng.uniform(-1, 1, (n, act_dim)).astype(np.float32),
        reward=np.arange(n, dtype=np.float32),
        next_obs=rng.standard_normal((n, obs_dim)).astype(np.float32),
        done=done,
        discount=np.full(n, 0.99, np.float32),
    )


def test_round_robin_insert_balances_shards(rng):
    buf = ShardedFusedReplay(64, 4, 2, _mesh(4), prioritized=True)
    assert buf.n_shards == 4 and buf.cap_shard == 16
    buf.add(_batch(rng, 10))
    buf.add(_batch(rng, 7))
    assert len(buf) == 17
    buf.drain()
    assert buf._size.sum() == 17
    assert buf._size.max() - buf._size.min() <= 1
    # every inserted reward value landed somewhere, exactly once
    rewards = np.sort(np.concatenate([
        np.asarray(buf.storage.reward[s, :buf._size[s]])
        for s in range(4)
    ]))
    np.testing.assert_array_equal(
        rewards, np.sort(np.concatenate([np.arange(10), np.arange(7)])))
    # trees: every live slot carries max_priority**alpha == 1
    for s in range(4):
        sz = int(buf._size[s])
        np.testing.assert_allclose(
            np.asarray(buf.trees.sum_tree[s, 1]), sz, rtol=1e-6)


def test_ring_wrap_per_shard(rng):
    buf = ShardedFusedReplay(16, 4, 2, _mesh(4), prioritized=False)
    for _ in range(3):
        buf.add(_batch(rng, 10))
        buf.drain()
    assert buf._size.sum() == 16  # full, wrapped
    assert all(buf._size == 4)


def test_sharded_fused_chunk_per(rng):
    mesh = _mesh(4)
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16, 16))
    state = init_state(config, jax.random.key(0))
    buf = ShardedFusedReplay(64, 4, 2, mesh, alpha=0.6)
    buf.add(_batch(rng, 64))
    buf.drain()
    fn = make_fused_chunk(config, mesh=mesh, k=3, batch_size=16,
                                  alpha=0.6, donate=False)
    s1, t1, m1 = fn(state, buf.trees, buf.storage, buf.size)
    assert int(jax.device_get(s1.step)) == 3
    assert m1["critic_loss"].shape == (3,)
    assert m1["td_error"].shape == (3, 16)
    assert np.isfinite(np.asarray(m1["critic_loss"])).all()
    # weights bounded by the global normalizer: max weight <= 1 (+eps)
    # run a fresh chunk (k=1) on untouched trees where all priorities are
    # equal -> all weights must be exactly 1
    fn1 = make_fused_chunk(config, mesh=mesh, k=1, batch_size=16,
                                   alpha=0.6, donate=False)
    _, _, m = fn1(state, buf.trees, buf.storage, buf.size)
    # recompute weights is internal; instead check determinism + tree change
    s2, t2, m2 = fn(state, buf.trees, buf.storage, buf.size)
    np.testing.assert_array_equal(np.asarray(m1["idx"]), np.asarray(m2["idx"]))
    np.testing.assert_array_equal(np.asarray(t1.sum_tree),
                                  np.asarray(t2.sum_tree))
    assert not np.allclose(np.asarray(t1.sum_tree),
                           np.asarray(buf.trees.sum_tree))


def test_sharded_fused_priorities_written_per_shard(rng):
    """k=1: each shard's tree leaves at the sampled local idx must equal
    (|td| + eps) ** alpha — td rows [i*b_local:(i+1)*b_local] belong to
    shard i by the P('data') layout."""
    mesh = _mesh(4)
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16, 16))
    state = init_state(config, jax.random.key(1))
    buf = ShardedFusedReplay(64, 4, 2, mesh, alpha=0.6)
    buf.add(_batch(rng, 64))
    buf.drain()
    fn = make_fused_chunk(config, mesh=mesh, k=1, batch_size=16,
                                  alpha=0.6, donate=False)
    _, trees, m = fn(state, buf.trees, buf.storage, buf.size)
    idx = np.asarray(m["idx"][0]).reshape(4, 4)   # [shard, b_local]
    td = np.asarray(m["td_error"][0]).reshape(4, 4)
    leaves = np.asarray(trees.sum_tree)[:, buf.cap_shard:]
    expect = (np.abs(td) + 1e-6) ** 0.6
    for s in range(4):
        for j, slot in enumerate(idx[s]):
            cands = expect[s][idx[s] == slot]
            assert np.any(np.isclose(leaves[s, slot], cands, rtol=1e-4))


def test_sharded_equal_priorities_weights_are_one(rng):
    """With every priority equal across all shards the IS weights must be
    exactly 1 regardless of beta — verified through the critic loss being
    identical to a run with beta0=1 (weights can only differ via w)."""
    mesh = _mesh(2)
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(8, 8))
    state = init_state(config, jax.random.key(2))
    buf = ShardedFusedReplay(32, 4, 2, mesh, alpha=0.6)
    buf.add(_batch(rng, 32))
    buf.drain()
    loss = {}
    for b0 in (0.4, 1.0):
        fn = make_fused_chunk(config, mesh=mesh, k=1, batch_size=8,
                                      alpha=0.6, beta0=b0, donate=False)
        _, _, m = fn(state, buf.trees, buf.storage, buf.size)
        loss[b0] = float(np.asarray(m["critic_loss"][0]))
    assert loss[0.4] == pytest.approx(loss[1.0], rel=1e-6)


def test_sharded_fused_uniform_chunk(rng):
    mesh = _mesh(4)
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(8, 8))
    state = init_state(config, jax.random.key(3))
    buf = ShardedFusedReplay(64, 4, 2, mesh, prioritized=False)
    buf.add(_batch(rng, 64))
    buf.drain()
    fn = make_fused_chunk(config, mesh=mesh, k=2, batch_size=16,
                          donate=False)
    s1, no_trees, m = fn(state, buf.trees, buf.storage, buf.size)
    assert buf.trees is None and no_trees is None
    assert int(jax.device_get(s1.step)) == 2
    idx = np.asarray(m["idx"])
    assert idx.min() >= 0 and idx.max() < buf.cap_shard


def test_sharded_drain_overflow_keeps_newest(rng):
    """A staged backlog past total capacity is trimmed to the newest
    `capacity` rows before the shard split (more than cap_shard rows on
    one shard would mean duplicate slots in a single scatter)."""
    buf = ShardedFusedReplay(16, 4, 2, _mesh(4), prioritized=False)
    for lo in (0, 11):
        b = _batch(rng, 11)
        b = TransitionBatch(*[np.asarray(v) for v in b])
        b = b._replace(reward=np.arange(lo, lo + 11, dtype=np.float32))
        buf.add(b)
    assert buf.drain() == 16
    assert buf._size.sum() == 16
    got = np.sort(np.concatenate([
        np.asarray(buf.storage.reward[s, :buf._size[s]]) for s in range(4)]))
    np.testing.assert_array_equal(got, np.arange(6, 22))


def test_sharded_state_dict_roundtrip(rng):
    mesh = _mesh(4)
    src = ShardedFusedReplay(64, 4, 2, mesh, alpha=0.6)
    src.add(_batch(rng, 40))
    src.drain()
    dst = ShardedFusedReplay(64, 4, 2, mesh, alpha=0.6)
    dst.load_state_dict(src.state_dict())
    np.testing.assert_array_equal(dst._size, src._size)
    np.testing.assert_array_equal(dst._head, src._head)
    assert dst._rr == src._rr
    np.testing.assert_allclose(np.asarray(dst.trees.sum_tree),
                               np.asarray(src.trees.sum_tree))
    np.testing.assert_array_equal(np.asarray(dst.storage.reward),
                                  np.asarray(src.storage.reward))


@pytest.mark.parametrize("kind", ("fused", "fused_snapshot", "sharded"))
def test_restored_trees_equal_the_live_ones_array_for_array(kind, rng):
    """A buffer restored from a state dict (``load_state_dict``) or a
    snapshot (``restore``) saved after a commit, a chunk's write-backs
    and another commit holds both trees bit for bit: the kept levels
    (``device_per.kept_levels``; the min tree's, ``min_kept_levels``: it
    has no leaves and is made of the sum tree's) as ``set_leaves`` left
    them step by step (rebuilt in one ``set_leaves`` by
    ``FusedDeviceReplay``, on the host by ``ShardedFusedReplay``), every
    other node what ``init`` gave it; the min root, all a reader reads of
    the min tree, is the smallest leaf saved."""
    from d4pg_tpu.replay import device_per as dper
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay

    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16, 16))
    state = init_state(config, jax.random.key(2))
    if kind != "sharded":
        make = lambda: FusedDeviceReplay(1024, 4, 2, alpha=0.6,  # noqa: E731
                                         block_rows=64)
        fn = make_fused_chunk(config, k=3, batch_size=16, alpha=0.6,
                              donate=False)
    else:
        mesh = _mesh(4)
        make = lambda: ShardedFusedReplay(1024, 4, 2, mesh,  # noqa: E731
                                          alpha=0.6)
        fn = make_fused_chunk(config, mesh=mesh, k=3, batch_size=16,
                                      alpha=0.6, donate=False)
    src = make()
    src.add(_batch(rng, 700))
    src.drain()
    _, src.trees, _ = fn(state, src.trees, src.storage, src.size)
    src.add(_batch(rng, 100))
    src.drain()
    dst = make()
    if kind == "fused_snapshot":
        saved = src.snapshot()
        dst.restore(saved)
    else:
        saved = src.state_dict()
        dst.load_state_dict(saved)
    cap = src.trees.sum_tree.shape[-1] // 2
    assert len(dper.kept_levels(cap)) >= 3
    fresh = dper.init(cap)
    assert src.trees.min_tree.shape[-1] == dper.min_tree_nodes(cap) < cap
    saved_leaves = np.asarray(
        (saved.get("sharded") or saved)["leaf_priorities"], np.float32)
    np.testing.assert_array_equal(  # over the shards, where there are any
        np.asarray(dst.trees.min_tree)[..., 1].min(),
        saved_leaves[saved_leaves > 0].min())
    for name, levels in (("sum_tree", dper.kept_levels(cap)),
                         ("min_tree", dper.min_kept_levels(cap)),
                         ("max_priority", None)):
        live = np.asarray(getattr(src.trees, name))
        np.testing.assert_array_equal(np.asarray(getattr(dst.trees, name)),
                                      live, err_msg=name)
        if levels is not None:
            kept = np.zeros(live.shape[-1], bool)
            for level in levels:
                kept[1 << level:2 << level] = True
            assert not kept.all()
            assert (live[..., kept] != np.asarray(
                getattr(fresh, name))[kept]).any(), name
            np.testing.assert_array_equal(
                live[..., ~kept], np.broadcast_to(
                    np.asarray(getattr(fresh, name))[~kept],
                    live[..., ~kept].shape), err_msg=name)


def test_sharded_checkpoint_rejected_by_flat_buffers(rng):
    """A sharded replay checkpoint restored into a non-sharded buffer must
    raise, not silently resume with an empty ring."""
    from d4pg_tpu.replay import PrioritizedReplayBuffer
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay

    src = ShardedFusedReplay(64, 4, 2, _mesh(4), alpha=0.6)
    src.add(_batch(rng, 20))
    src.drain()
    d = src.state_dict()
    with pytest.raises(ValueError, match="sharded"):
        PrioritizedReplayBuffer(64, 4, 2).load_state_dict(d)
    with pytest.raises(ValueError, match="sharded"):
        FusedDeviceReplay(64, 4, 2).load_state_dict(d)
    # and a different data-parallel degree is rejected too
    with pytest.raises(ValueError, match="data-parallel"):
        ShardedFusedReplay(64, 4, 2, _mesh(2)).load_state_dict(d)


def test_train_sharded_fused_end_to_end(tmp_path):
    """train() with --data_parallel 4 + device replay: the fused data
    plane lives on the mesh (no more host-tree fallback for multi-chip)."""
    from d4pg_tpu.config import ExperimentConfig
    from d4pg_tpu.train import train

    cfg = ExperimentConfig(
        env="point", max_steps=20, num_envs=2, warmup=100, n_epochs=1,
        n_cycles=2, episodes_per_cycle=1, train_steps_per_cycle=12,
        eval_trials=1, batch_size=16, memory_size=2000,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-5.0, v_max=0.0, replay_storage="device", fused_replay="on",
        data_parallel=4, updates_per_dispatch=8,
    )
    metrics = train(cfg)
    assert np.isfinite(metrics["critic_loss"])
