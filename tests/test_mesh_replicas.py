"""Mesh-native learner replicas (marker ``mesh``): the collective-merge
engine of ``learner/mesh_replicas.py`` against its two oracles —

1. N=1 through the mesh-native path is BITWISE the legacy FusedLoop:
   same pure ``fused_chunk_step`` under a singleton-axis ``shard_map``,
   merge as a Python-static identity (no arithmetic).
2. N-replica collective merges match the host-thread ``Aggregator`` on
   the same seeded stream: async (IMPACT lag-weighted fold) and sync
   (N-way average — float64 on the host, widest-available on device, so
   tolerance-grade, rtol 1e-6).

Plus the version-stream contract: merged rounds publish a monotone
version sequence through the same ``WeightStore`` the socket path uses.
"""

from __future__ import annotations

import glob
import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from d4pg_tpu.distributed.weights import WeightStore
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.aggregator import Aggregator
from d4pg_tpu.learner.loop import FusedLoop
from d4pg_tpu.learner.mesh_replicas import MeshReplicaGroup
from d4pg_tpu.learner.replica import PARAM_FIELDS, LearnerReplica, params_of
from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay
from d4pg_tpu.replay.uniform import TransitionBatch

pytestmark = pytest.mark.mesh

OBS, ACT, N_ROWS, STEPS = 5, 2, 96, 4


def _config():
    return D4PGConfig(obs_dim=OBS, act_dim=ACT, v_min=-10, v_max=10,
                      n_atoms=11, hidden=(16, 16))


def _batch(rng):
    return TransitionBatch(
        obs=rng.standard_normal((N_ROWS, OBS)).astype(np.float32),
        action=rng.uniform(-1, 1, (N_ROWS, ACT)).astype(np.float32),
        reward=rng.standard_normal(N_ROWS).astype(np.float32),
        next_obs=rng.standard_normal((N_ROWS, OBS)).astype(np.float32),
        done=np.zeros(N_ROWS, np.float32),
        discount=np.full(N_ROWS, 0.99, np.float32))


def _fill(batch):
    buf = FusedDeviceReplay(N_ROWS, OBS, ACT, alpha=0.6)
    buf.add(batch)
    buf.drain()
    return buf


def _replica_states(config, n):
    """train.py's replica construction: identical nets, decorrelated
    keys (replica 0 keeps the original chain)."""
    base = init_state(config, jax.random.key(0))
    states = []
    for i in range(n):
        # per-replica leaf copies: updates donate their input state, and
        # donated leaves shared between replicas would be deleted under
        # each other (the same guard train.py applies)
        rstate = jax.tree_util.tree_map(jnp.copy, base)
        if i:
            rstate = rstate._replace(key=jax.random.fold_in(rstate.key, i))
        states.append(rstate)
    return states


# ------------------------------------------------- N=1 bitwise oracle --

def test_n1_mesh_path_bitwise_equals_legacy_loop(rng):
    """ONE replica through the mesh-native engine — stacked state,
    shard_map'd chunk, collective-merge round — must land bit-for-bit
    the state the legacy fused loop produces."""
    config = _config()
    batch = _batch(rng)

    legacy = FusedLoop(config, _fill(batch), k=2, batch_size=8)
    legacy_state, _ = legacy.run(init_state(config, jax.random.key(0)),
                                 STEPS)

    group = MeshReplicaGroup(
        config, _replica_states(config, 1), k=2, batch_size=8)
    group.load(_fill(batch))
    group.run_round(STEPS)

    mesh_state = group.state_slice(0)
    for f in PARAM_FIELDS:
        a = jax.device_get(getattr(legacy_state, f))
        b = jax.device_get(getattr(mesh_state, f))
        jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
    np.testing.assert_array_equal(jax.device_get(legacy_state.step),
                                  jax.device_get(mesh_state.step))
    # and the merged tree IS the replica's params (identity merge)
    merged = group.merged_params()
    for f in PARAM_FIELDS:
        jax.tree_util.tree_map(
            np.testing.assert_array_equal,
            merged[f], jax.device_get(getattr(legacy_state, f)))
    group.close()


# --------------------------------------- N>1 vs the host aggregator ----

def _legacy_trees(config, batch, n):
    """Ground-truth per-replica streams: n independent legacy FusedLoops
    over identically-filled buffers, from the SAME decorrelated initial
    states train.py builds — the trees a round of thread replicas would
    submit."""
    states = _replica_states(config, n)
    trees = []
    for i in range(n):
        loop = FusedLoop(config, _fill(batch), k=2, batch_size=8)
        state, _ = loop.run(states[i], STEPS)
        trees.append(params_of(state))
    return trees


def _host_merge(trees, mode, clip=8.0):
    """The socket-path ground truth: a real host Aggregator receiving
    one round-synchronous round — every replica pulled the version-0
    basis, so replica i's submission arrives at lag i (async) or joins
    the N-way barrier (sync)."""
    agg = Aggregator(WeightStore(), mode=mode, clip=clip)
    epochs = [agg.register(i) for i in range(len(trees))]
    if mode == "sync":
        threads = [
            threading.Thread(
                target=agg.submit, args=(i, epochs[i], trees[i], 0),
                kwargs={"step": STEPS}, daemon=True)
            for i in range(len(trees))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
    else:
        for i, tree in enumerate(trees):
            res = agg.submit(i, epochs[i], tree, 0, step=STEPS)
            assert res["status"] == "applied" and res["lag"] == i
    _v, merged = agg.current()
    agg.close()
    return merged


def _mesh_round(config, batch, mode, n=2, clip=8.0):
    group = MeshReplicaGroup(
        config, _replica_states(config, n), k=2, batch_size=8,
        mode=mode, clip=clip)
    group.load(_fill(batch))
    group.run_round(STEPS)
    merged = group.merged_params()
    per_replica = [
        {f: jax.device_get(getattr(group.state_slice(i), f))
         for f in PARAM_FIELDS} for i in range(n)]
    group.close()
    return merged, per_replica


def _assert_tree_close(a, b, rtol, atol_ulps=0):
    """``atol_ulps``: an absolute floor of that many float32 ulps of each
    leaf's largest magnitude, beside the relative tolerance."""
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    assert len(flat_a) == len(flat_b)
    for x, y in zip(flat_a, flat_b):
        x, y = np.asarray(x), np.asarray(y)
        atol = atol_ulps * np.finfo(np.float32).eps * float(np.abs(x).max())
        np.testing.assert_allclose(x, y, rtol=rtol, atol=atol)


def test_per_replica_streams_match_legacy_loops(rng):
    """Before any merge semantics: replica i's trained params under the
    mesh engine must equal an independent legacy FusedLoop run from the
    same initial state over the same fill — BITWISE. This isolates the
    engine from the merge in the comparisons below. (The adoption step
    after the merge would perturb the stacked state, so the mesh side
    reads its per-replica slices before merging.)"""
    config = _config()
    batch = _batch(rng)
    legacy = _legacy_trees(config, batch, 2)

    group = MeshReplicaGroup(
        config, _replica_states(config, 2), k=2, batch_size=8)
    group.load(_fill(batch))
    group._fused_steps(STEPS)  # engine only — no merge/adopt yet
    for i, want in enumerate(legacy):
        got = {f: jax.device_get(getattr(group.state_slice(i), f))
               for f in PARAM_FIELDS}
        jax.tree_util.tree_map(np.testing.assert_array_equal, want, got)
    group.close()


def test_sync_collective_average_matches_host_aggregator(rng):
    """Sync mode: the on-device N-way average vs the host's float64
    averaging barrier, same seeded stream — within float64-grade
    tolerance (the device sums in the widest dtype it has)."""
    config = _config()
    batch = _batch(rng)
    host_merged = _host_merge(_legacy_trees(config, batch, 2), "sync")
    mesh_merged, _ = _mesh_round(config, batch, "sync")
    _assert_tree_close(host_merged, mesh_merged, rtol=1e-6)


def test_async_collective_fold_matches_host_aggregator(rng):
    """Async mode: the collective fold (adopt replica 0, blend replica i
    at w = max(1/(1+i), 1/clip)) vs the host aggregator receiving the
    same round-synchronous submissions in replica order."""
    config = _config()
    batch = _batch(rng)
    host_merged = _host_merge(_legacy_trees(config, batch, 3), "async")
    mesh_merged, _ = _mesh_round(config, batch, "async", n=3)
    # The host fold and the on-device collective add the same float32
    # terms in different orders, so an element that nearly cancels (1.5e-5
    # in a leaf whose largest is 3e-3) differs by rounding of the TERMS,
    # not of the result: under one ulp of the leaf's largest magnitude in
    # every leaf (4.0e-11 here), which a relative tolerance alone cannot
    # express. The bitwise assertions of this file stay bitwise.
    _assert_tree_close(host_merged, mesh_merged, rtol=1e-6, atol_ulps=4)


# ------------------------------------------------- version stream ------

def test_merge_rounds_publish_monotone_versions(rng):
    config = _config()
    store = WeightStore()
    group = MeshReplicaGroup(
        config, _replica_states(config, 2), k=2, batch_size=8,
        mode="async", store=store,
        extract=lambda tree: tree["actor_params"])
    group.load(_fill(_batch(rng)))
    for _ in range(3):
        group.run_round(2)
    assert group.versions == sorted(group.versions)
    assert len(group.versions) == 3
    # the store's latest pull is the last merged actor tree
    version, params = store.get()
    assert version == group.versions[-1]
    merged = group.merged_params()
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        params, merged["actor_params"])
    group.close()


# ------------------------------------------------- guards --------------

def test_bad_mode_and_clip_rejected():
    config = _config()
    with pytest.raises(ValueError):
        MeshReplicaGroup(config, _replica_states(config, 1), k=2,
                         batch_size=8, mode="hogwild")
    with pytest.raises(ValueError):
        MeshReplicaGroup(config, _replica_states(config, 1), k=2,
                         batch_size=8, clip=0.5)


def test_run_round_before_load_raises():
    config = _config()
    group = MeshReplicaGroup(config, _replica_states(config, 1), k=2,
                             batch_size=8)
    with pytest.raises(RuntimeError):
        group.run_round(2)


# ------------------------------------------------- artifact gate -------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.obs
def test_fleet_artifact_mesh_learners_schema():
    """The newest committed fleet artifact must carry the mesh_learners
    block: the socket-vs-collective aggregation A/B at equal offered
    load per replica count, with updates/s on BOTH arms and per-round
    aggregation latency percentiles — the measurement attributing the
    mesh-native transport's win. A later PR that drops it fails tier-1
    here."""
    arts = sorted(glob.glob(os.path.join(
        REPO_ROOT, "docs", "evidence", "fleet", "fleet_*.json")))
    assert arts, "no committed fleet artifact"
    with open(arts[-1]) as f:
        artifact = json.load(f)
    blk = artifact.get("mesh_learners")
    assert blk, "newest fleet artifact lost its mesh_learners block"
    assert blk["metric"] == "fleet_mesh_learners" and blk["schema"] == 1
    assert "error" not in blk, blk.get("error")
    assert blk["sweep"], "mesh_learners sweep is empty"
    for row in blk["sweep"]:
        assert row["metric"] == "mesh_learners_ab" and row["schema"] == 1
        assert row["n_replicas"] >= 1
        for arm in ("socket", "collective"):
            assert row[arm]["updates_per_sec"] > 0
            assert row[arm]["agg_latency_s"]["p50"] is not None
            assert row[arm]["agg_latency_s"]["p95"] is not None
        # both arms ran the SAME offered load — that's what makes the
        # comparison an attribution, not a vibe
        assert row["load"]["rounds"] > 0
        assert row["load"]["steps_per_round"] > 0
