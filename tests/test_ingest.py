"""Ingest-plane tests: the batched block drain (replay/fused_buffer.py +
device_ring.block_write), the overlapped ≤1-H2D-per-chunk schedule
(learner/pipeline.IngestOverlap), the coalescing transport, and what
``ExperimentConfig.learner_config`` builds. The per-row drain the block path replaced is
kept as the bitwise oracle (``drain_per_row``)."""

import threading
import time

import jax
import numpy as np
import pytest

from d4pg_tpu.distributed.replay_service import ReplayService
from d4pg_tpu.io.profiling import TransferSentinel
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.pipeline import IngestOverlap
from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay, HostStagingRing
from d4pg_tpu.replay.uniform import TransitionBatch

OBS, ACT = 5, 2


def _batch(rng, n, obs=OBS, act=ACT):
    return TransitionBatch(
        obs=rng.standard_normal((n, obs)).astype(np.float32),
        action=rng.uniform(-1, 1, (n, act)).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        next_obs=rng.standard_normal((n, obs)).astype(np.float32),
        done=np.zeros(n, np.float32),
        discount=np.full(n, 0.99, np.float32),
    )


# ------------------------------------------------------- block drain ------

def test_block_drain_bitwise_equals_per_row(rng):
    """Same rows through the block path and the old per-row path must land
    the SAME bytes in the ring and the SAME priorities in the trees."""
    a = FusedDeviceReplay(96, OBS, ACT, block_rows=32)
    b = FusedDeviceReplay(96, OBS, ACT, block_rows=32)
    for n in (33, 64, 7, 100, 128, 5):  # partials, full blocks, > capacity
        batch = _batch(rng, n)
        a.add(batch)
        b.add(batch)
    assert a.drain() == b.drain_per_row()
    assert (a.size, a.head) == (b.size, b.head)
    for f in range(len(a.storage)):
        np.testing.assert_array_equal(
            np.asarray(a.storage[f][:96]), np.asarray(b.storage[f][:96]))
    np.testing.assert_array_equal(np.asarray(a.trees.sum_tree),
                                  np.asarray(b.trees.sum_tree))
    np.testing.assert_array_equal(np.asarray(a.trees.min_tree),
                                  np.asarray(b.trees.min_tree))
    # the min tree has no leaves: both made it from the sum tree's
    assert a.trees.min_tree.shape == (2,) and a.trees.capacity == 128
    assert float(a.trees.min_tree[1]) == float(
        np.asarray(a.trees.sum_tree[128:128 + a.size]).min()) > 0


def test_block_drain_wraparound_at_capacity_boundary(rng):
    """Blocks that straddle the ring end must wrap exactly (the two-slice
    shadow-mirror path), matching a sequential host oracle."""
    cap = 50
    buf = FusedDeviceReplay(cap, OBS, ACT, prioritized=False, block_rows=16)
    host = np.zeros((cap, OBS), np.float32)
    head = size = 0
    for n in (10, 40, 23, cap, 9, 64):  # 64 > capacity: oldest overwritten
        batch = _batch(rng, n)
        buf.add(batch)
        buf.drain()
        for i in range(n):
            host[head] = batch.obs[i]
            head = (head + 1) % cap
            size = min(size + 1, cap)
    assert (buf.head, buf.size) == (head, size)
    np.testing.assert_array_equal(np.asarray(buf.storage.obs[:cap]), host)


def test_partial_final_block(rng):
    """A drain whose last block is partially filled lands exactly the
    valid rows; the masked scratch rows past ``n`` touch nothing."""
    buf = FusedDeviceReplay(64, OBS, ACT, block_rows=16)
    batch = _batch(rng, 21)  # one full block + 5-row partial
    buf.add(batch)
    assert buf.drain() == 21
    assert (buf.size, buf.head) == (21, 21)
    np.testing.assert_array_equal(np.asarray(buf.storage.obs[:21]), batch.obs)
    # untouched slots stay zero-initialized
    assert not np.asarray(buf.storage.obs[21:64]).any()
    cap = buf.trees.capacity
    leaves = np.asarray(buf.trees.sum_tree[cap:cap + 64])
    assert (leaves[:21] > 0).all() and not leaves[21:].any()


def test_interleaved_drain_and_fused_chunk_preserves_priorities(rng):
    """drain -> chunk -> drain: the chunk's TD write-backs survive the next
    block insert untouched; inserted slots get max_priority ** alpha."""
    config = D4PGConfig(obs_dim=OBS, act_dim=ACT, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16))
    buf = FusedDeviceReplay(128, OBS, ACT, alpha=0.6, block_rows=32)
    buf.add(_batch(rng, 64))
    buf.drain()
    fn = make_fused_chunk(config, k=2, batch_size=8, alpha=0.6, donate=False)
    state = init_state(config, jax.random.key(0))
    state, buf.trees, m = fn(state, buf.trees, buf.storage, buf.size)
    cap = buf.trees.capacity
    after_chunk = np.asarray(buf.trees.sum_tree[cap:cap + 128])
    head0 = buf.head
    buf.add(_batch(rng, 32))
    assert buf.drain() == 32
    leaves = np.asarray(buf.trees.sum_tree[cap:cap + 128])
    inserted = (head0 + np.arange(32)) % 128
    expected = float(np.asarray(buf.trees.max_priority)) ** 0.6
    np.testing.assert_allclose(leaves[inserted], expected, rtol=1e-6)
    untouched = np.setdiff1d(np.arange(128), inserted)
    np.testing.assert_array_equal(leaves[untouched], after_chunk[untouched])
    # and the chunk still samples fine afterwards
    state, buf.trees, m = fn(state, buf.trees, buf.storage, buf.size)
    assert np.isfinite(np.asarray(m["critic_loss"])).all()


def test_overlap_le_one_h2d_per_chunk(rng):
    """The shipped overlap schedule (commit -> dispatch -> stage) makes at
    most ONE explicit device_put per fused chunk."""
    config = D4PGConfig(obs_dim=OBS, act_dim=ACT, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16))
    buf = FusedDeviceReplay(256, OBS, ACT, alpha=0.6, block_rows=32)
    service = ReplayService(buf)
    ingest = IngestOverlap(service)
    fn = make_fused_chunk(config, k=2, batch_size=8, alpha=0.6, donate=True)
    state = init_state(config, jax.random.key(0))
    service.add(_batch(rng, 64))
    service.flush()
    ingest.flush()
    state, buf.trees, m = fn(state, buf.trees, buf.storage,
                             buf.size)  # warmup/compile
    n_chunks = 6
    with TransferSentinel() as t:
        for _ in range(n_chunks):
            ingest.commit()
            state, buf.trees, m = fn(state, buf.trees, buf.storage,
                                     buf.size)
            service.add(_batch(rng, 32))
            service.flush()
            ingest.stage()
    assert t.h2d <= n_chunks
    # every staged row is committed or still in flight (the initial 64
    # rode the pre-loop flush, which commits without staging)
    assert ingest.rows_staged == (ingest.rows_committed - 64) + 32
    ingest.flush()
    assert len(buf) == 64 + n_chunks * 32
    service.close()


def test_staging_ring_bounded_drops_oldest(rng):
    ring = HostStagingRing([((OBS,), np.float32), ((ACT,), np.float32),
                            ((), np.float32), ((OBS,), np.float32),
                            ((), np.float32), ((), np.float32)],
                           block_rows=8, n_blocks=2)  # bound: 16 rows
    first, second = _batch(rng, 10), _batch(rng, 10)
    ring.push(first)
    ring.push(second)  # 20 staged > 16: the 4 oldest drop
    assert len(ring) == 16
    frames = []
    while True:
        views, n = ring.frame()
        if n == 0:
            break
        frames.append(views.obs[:n].copy())
        ring.pop(n)
    got = np.concatenate(frames)
    want = np.concatenate([first.obs, second.obs])[-16:]
    np.testing.assert_array_equal(got, want)


# ------------------------------------------- sharded multi-ring staging ---

def test_multi_ring_merge_bitwise_equals_single_ring_and_per_row(rng):
    """The sharded staging plane (K private rings + ticket-ordered merge,
    ``staging.MultiRingStaging``) must land EXACTLY the bytes and
    priorities of the single-ring path AND the per-row oracle — the
    merge-commit reorders nothing at quiescence."""
    a = FusedDeviceReplay(96, OBS, ACT, block_rows=32)
    b = FusedDeviceReplay(96, OBS, ACT, block_rows=32, ingest_shards=2)
    c = FusedDeviceReplay(96, OBS, ACT, block_rows=32, ingest_shards=2)
    for rnd in range(4):  # several rounds; the ring wraps capacity
        for t, n in enumerate((13, 24, 7, 30, 9)):  # stays within staging
            batch = _batch(rng, n)
            ticket = rnd * 10 + t
            a.add(batch)
            b.add_sharded(batch, shard=t % 2, ticket=ticket)
            c.add_sharded(batch, shard=t % 2, ticket=ticket)
        assert a.drain() == b.drain() == c.drain_per_row()
    assert (a.size, a.head) == (b.size, b.head) == (c.size, c.head)
    for f in range(len(a.storage)):
        np.testing.assert_array_equal(
            np.asarray(a.storage[f][:96]), np.asarray(b.storage[f][:96]))
        np.testing.assert_array_equal(
            np.asarray(b.storage[f][:96]), np.asarray(c.storage[f][:96]))
    np.testing.assert_array_equal(np.asarray(a.trees.sum_tree),
                                  np.asarray(b.trees.sum_tree))
    np.testing.assert_array_equal(np.asarray(b.trees.sum_tree),
                                  np.asarray(c.trees.sum_tree))


def test_service_direct_stage_k2_bitwise_equals_k1(rng):
    """End to end through the service: a K=2 ``ReplayService`` over a
    sharded fused buffer engages the direct-stage fast path (workers
    copy rows into their own ring, no buffer lock) and must still land
    the identical device state as the K=1 plane."""
    from d4pg_tpu.obs.registry import REGISTRY

    admitted0 = REGISTRY.counter("ingest.rows_admitted").value
    committed0 = REGISTRY.counter("ingest.rows_committed").value
    f1 = FusedDeviceReplay(256, OBS, ACT, block_rows=32)
    f2 = FusedDeviceReplay(256, OBS, ACT, block_rows=32, ingest_shards=2)
    s1 = ReplayService(f1)
    s2 = ReplayService(f2, num_ingest_shards=2)
    assert s2._direct_stage, "direct-stage fast path must engage"
    batches = [_batch(rng, n) for n in (8, 3, 16, 5, 12, 7, 9, 4)]
    for i, b in enumerate(batches):
        s1.add(b)
        s2.add(b, shard=i % 2)
    s1.flush()
    s2.flush()
    assert s1.drain_device() == s2.drain_device()
    assert s1.env_steps == s2.env_steps
    for f in range(len(f1.storage)):
        np.testing.assert_array_equal(np.asarray(f1.storage[f][:64]),
                                      np.asarray(f2.storage[f][:64]))
    np.testing.assert_array_equal(np.asarray(f1.trees.sum_tree),
                                  np.asarray(f2.trees.sum_tree))
    # counter-total bitwise equivalence (the no-double-count contract):
    # the K=2 service ran every row through add_sharded's direct-stage
    # fast path (staged_rows == 64), but its row LEDGER must be
    # identical to K=1's — rows_committed counts each row once at the
    # ordered commit, never again at staging; naive "rows_in +
    # staged_rows" style aggregation would report the fast path twice.
    st1, st2 = s1.ingest_stats(), s2.ingest_stats()
    assert sum(p["staged_rows"] for p in st2["per_shard"]) == 64
    assert sum(p["staged_rows"] for p in st1["per_shard"]) == 0
    assert st1["rows_committed"] == st2["rows_committed"] == 64
    assert sum(p["rows_in"] for p in st1["per_shard"]) \
        == sum(p["rows_in"] for p in st2["per_shard"]) == 64
    # ...and the process-wide registry ledger agrees: exactly 2x64 rows
    # admitted AND committed across the two services, no fast-path echo
    assert REGISTRY.counter("ingest.rows_admitted").value \
        - admitted0 == 128
    assert REGISTRY.counter("ingest.rows_committed").value \
        - committed0 == 128
    s1.close()
    s2.close()


# -------------------------------------------- transport coalescing --------

def test_coalescing_sender_batches_frames(rng):
    from d4pg_tpu.distributed.transport import (
        CoalescingSender, TransitionReceiver)

    frames: list[tuple[TransitionBatch, bool]] = []
    got = threading.Event()

    def on_batch(batch, actor_id, count):
        frames.append((batch, count))
        got.set()

    recv = TransitionReceiver(on_batch)
    sender = CoalescingSender("127.0.0.1", recv.port, actor_id="c0",
                              min_block=64, max_block=256,
                              flush_interval=60.0)
    sent = [_batch(rng, 10) for _ in range(8)]
    try:
        for b in sent:
            sender.send(b)  # 80 rows: one 64-row flush, 16 left pending
        sender.flush()
        deadline = time.monotonic() + 5.0
        while sum(f[0].obs.shape[0] for f in frames) < 80:
            assert time.monotonic() < deadline, "coalesced rows not delivered"
            time.sleep(0.01)
    finally:
        sender.close()
        recv.close()
    # 8 sends rode in ≤ 3 wire frames (coalesced), rows in order
    assert 1 <= len(frames) <= 3
    got_rows = np.concatenate([np.asarray(f[0].obs) for f in frames])
    np.testing.assert_array_equal(
        got_rows, np.concatenate([b.obs for b in sent]))


def test_coalescing_sender_splits_count_flag(rng):
    """HER relabels (count_env_steps=False) must not merge into a frame
    with real env rows — the flag is frame-granular on the wire."""
    from d4pg_tpu.distributed.transport import (
        CoalescingSender, TransitionReceiver)

    frames = []

    def on_batch(batch, actor_id, count):
        frames.append((batch.obs.shape[0], count))

    recv = TransitionReceiver(on_batch)
    sender = CoalescingSender("127.0.0.1", recv.port, min_block=256,
                              max_block=256, flush_interval=60.0)
    try:
        sender.send(_batch(rng, 5), count_env_steps=True)
        sender.send(_batch(rng, 3), count_env_steps=False)  # forces a flush
        sender.send(_batch(rng, 2), count_env_steps=False)
        sender.flush()
        deadline = time.monotonic() + 5.0
        while sum(n for n, _ in frames) < 10:
            assert time.monotonic() < deadline
            time.sleep(0.01)
    finally:
        sender.close()
        recv.close()
    assert frames == [(5, True), (5, False)]


def test_replay_service_coalesced_ingest_counts_env_steps(rng):
    buf = FusedDeviceReplay(256, OBS, ACT, block_rows=32)
    service = ReplayService(buf)
    for i in range(10):
        service.add(_batch(rng, 7), count_env_steps=(i % 2 == 0))
    service.flush()
    assert service.env_steps == 5 * 7  # only the counted half
    assert len(service) == 70
    service.close()


# ------------------------------------------- what learner_config builds ----

@pytest.mark.parametrize("backend", [None, "tpu"])
def test_config_auto_resolves_before_learner_config(monkeypatch, backend):
    """``learner_config`` builds the one projection and measures nothing,
    on any backend: no start-up timing pass, no compile."""
    from d4pg_tpu.config import ExperimentConfig
    from d4pg_tpu.io.profiling import RecompileSentinel

    if backend is not None:
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = ExperimentConfig(env="point", v_min=-10.0, v_max=10.0)
    with RecompileSentinel() as compiles:
        config = cfg.learner_config(OBS, ACT)
    assert config.projection == "einsum"
    assert compiles.compilations == 0


def test_train_flags_build_the_measured_learner_config():
    """What ``train.main`` builds from the flags for the sizes
    ``benchmark/configs/humanoid-mlp.json`` names is, field for field of
    that file's ``model`` block, the ``D4PGConfig`` the benchmark's cells
    measure."""
    import json
    import os

    from benchmark import cellbuild
    from d4pg_tpu.config import parse_args

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "humanoid-mlp.json")) as f:
        cell = json.load(f)
    want = cellbuild.learner_config(cell)
    got = parse_args(["--env", "Humanoid-v4", "--v_min", "0", "--v_max",
                      "800", "--compute_dtype", "bfloat16"]
                     ).learner_config(376, 17)
    for field in cell["model"]:
        assert getattr(got, field) == getattr(want, field), field
