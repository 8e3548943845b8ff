"""Device-resident PER sampling (replay/device_per.py descent +
replay/device_sampler.DeviceSampleDealer).

The load-bearing oracle is the seeded-stream lockstep: the device dealer
and its float32 host twin (``SampleDealer(scheme='device')`` — numpy
float32 trees, device stratification, the SHARED compiled weight
transform) consume identical RNG streams, so same seed must give
bitwise-identical ``(idx, weights, beta, rows, gen)``. The twin is
pinned against the float64 legacy descent separately, on dyadic-rational
priorities where float32 arithmetic is exact.

Tie rule (documented in ``device_per.descend`` and pinned here): at
every node, ``mass >= left_subtree_sum`` descends RIGHT — a mass equal
to a cumulative prefix boundary selects the first leaf AFTER the
boundary, so a zero-priority run at a boundary is skipped, never
sampled. All three implementations (f64 host, f32 twin, device) share
it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.device_sampler import DeviceSampleDealer
from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay
from d4pg_tpu.replay.prioritized import PrioritizedReplayBuffer
from d4pg_tpu.replay.sampler import SampleDealer, ShardSlicePerTrees
from d4pg_tpu.replay.schedule import SharedBetaSchedule
from d4pg_tpu.replay.segment_tree import SumTree
from d4pg_tpu.replay.staging import DealtBlockRing, DeviceDealtBlockRing
from d4pg_tpu.replay.uniform import TransitionBatch

pytestmark = pytest.mark.devsample

CAP, K, B, OD, AD = 128, 2, 8, 4, 2


def _mk_batch(rng, n):
    return TransitionBatch(
        rng.random((n, OD)).astype(np.float32),
        rng.random((n, AD)).astype(np.float32),
        rng.random(n).astype(np.float32),
        rng.random((n, OD)).astype(np.float32),
        (rng.random(n) < 0.1).astype(np.float32),
        np.full(n, 0.99, np.float32))


def _device_rig(seed=42, ring_cls=DealtBlockRing, **kw):
    buf = FusedDeviceReplay(CAP, OD, AD, alpha=0.6, gen_tracked=True,
                            block_rows=32)
    ring = ring_cls(4)
    dealer = DeviceSampleDealer(CAP, [ring], k=K, batch_size=B, alpha=0.6,
                                beta_schedule=SharedBetaSchedule(),
                                min_size=8, seed=seed, **kw)
    dealer.resync(buf)
    return buf, ring, dealer


def _twin_rig(seed=42):
    buf = PrioritizedReplayBuffer(CAP, OD, AD, alpha=0.6, seed=0)
    ring = DealtBlockRing(4)
    dealer = SampleDealer(CAP, [ring], n_shards=1, k=K, batch_size=B,
                          alpha=0.6, beta_schedule=SharedBetaSchedule(),
                          min_size=8, seed=seed, scheme="device")
    dealer.resync(buf)
    return buf, ring, dealer


# --------------------------------------------- the seeded-stream oracle


def test_device_dealer_bitwise_equals_host_twin(rng):
    """Same seed, same ingest stream, same write-backs => the device
    dealer's blocks are BITWISE the host twin's: idx, weights, gen,
    beta/step, and every gathered row. Zero tolerance — the contract is
    equality of the sample STREAM, not distributional closeness."""
    dbuf, _dring, dd = _device_rig()
    hbuf, _hring, hd = _twin_rig()
    dealt_total = 0
    for step in range(6):
        batch = _mk_batch(rng, 10)
        dealt_d = dd.ingest_and_deal([(dbuf.add(batch), None, None)], dbuf)
        dealt_h = hd.ingest_and_deal([(hbuf.add(batch), None, None)], hbuf)
        assert len(dealt_d) == len(dealt_h)
        for (_ri, bd), (_rh, bh) in zip(dealt_d, dealt_h):
            np.testing.assert_array_equal(np.asarray(bd.idx), bh.idx)
            np.testing.assert_array_equal(np.asarray(bd.weights),
                                          bh.weights)
            assert bd.beta == bh.beta and bd.step == bh.step
            np.testing.assert_array_equal(np.asarray(bd.gen), bh.gen)
            for da, ha in zip(bd.batches, bh.batches):
                np.testing.assert_array_equal(np.asarray(da), ha)
            # identical TD write-backs keep the trees in lockstep too
            td = np.random.default_rng(step).uniform(
                0.1, 2.0, bh.idx.shape)
            dd.queue_writeback(bd.idx, td, bd.gen)
            hd.queue_writeback(bh.idx, td, bh.gen)
            dealt_total += 1
        dd.publish(dealt_d)
        hd.publish(dealt_h)
    assert dealt_total >= 4  # the oracle actually exercised deals


def test_twin_trees_match_f64_legacy_on_dyadic_priorities(rng):
    """The float32 twin tree vs the float64 legacy tree, on
    dyadic-rational priorities (k/16, k < 2**10) where every f32 sum is
    exact: identical descents for dyadic masses across the whole total
    range. This pins the twin to the legacy math where exactness is
    possible — the f32-vs-f64 gap on arbitrary reals is a rounding
    fact, not a defect, and is why the ORACLE twin is f32."""
    t32 = ShardSlicePerTrees(CAP, 1, dtype=np.float32)
    t64 = ShardSlicePerTrees(CAP, 1)
    idx = np.arange(CAP)
    pri = rng.integers(1, 1024, size=CAP).astype(np.float64) / 16.0
    t32.set(idx, pri)
    t64.set(idx, pri)
    assert t32.total() == t64.total()
    mass = (rng.integers(0, int(t64.total() * 16), size=256)
            .astype(np.float64) / 16.0)
    np.testing.assert_array_equal(t32.find_prefixsum(mass),
                                  t64.find_prefixsum(mass))


# ------------------------------------- descent edge-case property pins


def _host_ref(values):
    s = SumTree(len(values))
    s.set(np.arange(len(values)), np.asarray(values, np.float64))
    return s


def test_descent_all_zero_priorities():
    """All-zero tree: every left_sum is 0, and the tie rule
    (mass >= left_sum -> RIGHT) walks to the LAST leaf at every level —
    device and host agree, and the caller's size clamp then maps it
    into the live region. No NaNs, no index out of range."""
    cap = 16
    host = _host_ref(np.zeros(cap))
    trees = dper.init(cap)
    mass = np.array([0.0, 0.5, 1.0], np.float32)
    got = np.asarray(dper.descend(trees.sum_tree, jnp.asarray(mass)))
    np.testing.assert_array_equal(got, host.find_prefixsum(mass))
    np.testing.assert_array_equal(got, [cap - 1] * 3)
    # the deal-path clamp keeps the all-zero draw inside the live rows
    clamped = np.asarray(dper.sample_from_uniforms(
        trees, jnp.zeros((3,)), jnp.int32(5)))
    assert clamped.max() <= 4


def test_descent_capacity_boundary_wraparound(rng):
    """A commit block that wraps the capacity boundary must land its
    priorities in the wrapped slots — leaf writes go through
    ``(start + row) % capacity``, and the descent then sees exactly the
    host reference tree built from the same wrapped assignment."""
    buf = FusedDeviceReplay(12, OD, AD, alpha=0.6, gen_tracked=True,
                            block_rows=8)
    filler = _mk_batch(rng, 8)
    slots = []
    for _ in range(2):  # 16 rows into 12 slots: the 2nd block wraps
        slots.append(buf.add(filler))
        buf.drain()
    assert slots[1][-1] < slots[1][0]  # genuinely wrapped
    p = float(buf.max_priority) ** 0.6
    host = np.zeros(dper.init(12).capacity)
    host[np.concatenate(slots) % 12] = np.float32(p)
    ref = _host_ref(host)
    mass = (rng.random(64) * ref.sum()).astype(np.float32)
    got = np.asarray(dper.descend(buf.trees.sum_tree, jnp.asarray(mass)))
    np.testing.assert_array_equal(got, ref.find_prefixsum(mass))
    # wrapped slots were double-written: their generation advanced twice
    gen = np.asarray(buf.gen)
    wrapped = slots[1][slots[1] < slots[1][0]]
    assert (gen[wrapped] == 2).all()
    assert int(buf.size) == 12


def test_descent_single_leaf_tree():
    """capacity=1 degenerates to a two-node tree: zero descent levels,
    every mass maps to leaf 0 — device and host agree."""
    host = _host_ref([3.0])
    trees = dper.set_leaves(dper.init(1), jnp.array([0]),
                            jnp.array([3.0], jnp.float32))
    mass = np.array([0.0, 1.5, 2.999], np.float32)
    got = np.asarray(dper.descend(trees.sum_tree, jnp.asarray(mass)))
    np.testing.assert_array_equal(got, host.find_prefixsum(mass))
    np.testing.assert_array_equal(got, [0, 0, 0])


def test_descent_tie_rule_on_duplicate_prefixes():
    """Duplicate cumulative prefixes (zero-priority runs): leaves
    [1, 0, 0, 1] have prefix sums [1, 1, 1, 2]. The documented tie rule
    (mass >= left_sum -> RIGHT) sends mass exactly 1.0 PAST the zero
    run to leaf 3 — a zero-priority leaf is never selected by a
    boundary mass. Device and the f64 host reference agree bitwise."""
    vals = [1.0, 0.0, 0.0, 1.0]
    host = _host_ref(vals)
    trees = dper.set_leaves(dper.init(4), jnp.arange(4),
                            jnp.asarray(vals, jnp.float32))
    mass = np.array([0.0, 0.5, 1.0, 1.5], np.float32)
    got = np.asarray(dper.descend(trees.sum_tree, jnp.asarray(mass)))
    np.testing.assert_array_equal(got, host.find_prefixsum(mass))
    np.testing.assert_array_equal(got, [0, 0, 3, 3])


# ------------------------------------- write-back fencing, device tree


def test_generation_fenced_writeback_lands_in_device_tree(rng):
    """A live write-back must land ``td ** alpha`` (host-side pow, f32)
    in the DEVICE sum tree's leaf; a stale-generation write-back for a
    since-overwritten slot must be dropped and counted, leaving the
    leaf at its commit-time priority."""
    buf, _ring, dealer = _device_rig()
    dealer.ingest_and_deal([(buf.add(_mk_batch(rng, 16)), None, None)],
                           buf)
    live_slot, stale_slot = 3, 7
    gen_live = np.asarray(buf.gen)[live_slot]
    # stale: stamped one generation behind the slot's current one
    dealer.queue_writeback(np.array([stale_slot]), np.array([9.0]),
                           np.array([np.asarray(buf.gen)[stale_slot] - 1]))
    dealer.queue_writeback(np.array([live_slot]), np.array([2.0]),
                           np.array([gen_live]))
    dealer.ingest_and_deal((), buf)  # idle tick settles the queue
    leaf = np.asarray(buf.trees.sum_tree)[buf.trees.capacity + live_slot]
    assert leaf == np.float32(2.0 ** 0.6)  # host pow, cast f32
    stale_leaf = np.asarray(
        buf.trees.sum_tree)[buf.trees.capacity + stale_slot]
    assert stale_leaf == np.float32(1.0)  # untouched commit priority
    assert dealer.writeback_dropped_stale == 1
    assert dealer.max_priority == pytest.approx(2.0)
    assert buf.max_priority == pytest.approx(2.0)


def test_device_ring_clear_deletes_dropped_blocks(rng):
    """DeviceDealtBlockRing.clear (the replica-kill path) must eagerly
    delete the dropped blocks' device buffers — dead sample HBM is
    reclaimed at the kill instant, not at the next GC cycle."""
    buf, ring, dealer = _device_rig(ring_cls=DeviceDealtBlockRing)
    dealer.publish(dealer.ingest_and_deal(
        [(buf.add(_mk_batch(rng, 16)), None, None)], buf))
    blocks = list(ring._q)
    assert blocks, "dealer never dealt"
    held = [a for blk in blocks
            for a in (*blk.batches, blk.weights, blk.idx, blk.gen)]
    assert ring.clear() == len(blocks)
    assert all(a.is_deleted() for a in held)
    # the buffer's own arrays must NOT be collateral damage
    assert not buf.trees.sum_tree.is_deleted()
    jax.block_until_ready(buf.storage.obs)


# ------------------------------------------------- runtime sentinels


def test_deal_dispatch_sentinels(rng):
    """The tentpole's transfer story, pinned: after warmup the
    ingest+deal loop must show ZERO recompiles, explicit H2D only for
    staged actor frames (never sampled rows), and the compiled deal
    dispatch must contain ZERO resharding collectives."""
    from d4pg_tpu.io.profiling import (RecompileSentinel, ReshardSentinel,
                                       TransferSentinel)

    buf, ring, dealer = _device_rig()
    feed = _mk_batch(rng, 16)
    dealer.publish(dealer.ingest_and_deal([(buf.add(feed), None, None)],
                                          buf))
    while ring.pop(timeout=0) is not None:
        pass
    rounds = 6
    with RecompileSentinel() as rec, TransferSentinel() as tr:
        for _ in range(rounds):
            dealer.publish(dealer.ingest_and_deal(
                [(buf.add(feed), None, None)], buf))
            while ring.pop(timeout=0) is not None:
                pass
        jax.block_until_ready(buf.trees.sum_tree)
    rec.assert_clean("device ingest+deal steady state")
    assert tr.h2d <= rounds, (
        f"{tr.h2d} explicit H2D over {rounds} ticks — sampled rows must "
        "never cross host->device")
    resh = ReshardSentinel()
    u = np.zeros((dealer.k, dealer.batch_size), np.float32)
    resh.inspect(dealer.deal_fn, buf.storage, buf.trees.sum_tree,
                 buf.trees.min_tree, buf.gen, u, np.int32(buf.size))
    resh.assert_clean("device deal dispatch")
    assert resh.steady_state_reshards == 0
    # the deal hands on the min tree's root, all it reads of that tree
    # (which has no leaves): the smallest live leaf of the sum tree
    cap = buf.trees.capacity
    out = dealer.deal_fn(buf.storage, buf.trees.sum_tree,
                         buf.trees.min_tree, buf.gen, u, np.int32(buf.size))
    assert float(out[-1]) == float(
        np.asarray(buf.trees.sum_tree[cap:cap + buf.size]).min()) > 0


# ----------------------------------------------- chaos smoke (device)


@pytest.mark.fleet
def test_device_sampler_chaos_smoke():
    """The device arm under the sampler fault set (consumer kill +
    stale-generation injection + sender chaos): every gating oracle
    holds and the broad top-frame containments never fire
    (contained_crashes delta 0)."""
    from d4pg_tpu.fleet.sampler_chaos import (SamplerChaosConfig,
                                              run_sampler_chaos)
    from d4pg_tpu.obs.registry import REGISTRY

    crashes0 = REGISTRY.counter("threads.contained_crashes").value
    rep = run_sampler_chaos(SamplerChaosConfig(
        sample_path="device", n_actors=4, duration_s=2.5,
        rows_per_sec=40.0, learner_kills=1, stale_frames=2, seed=5))
    assert REGISTRY.counter("threads.contained_crashes").value == crashes0
    assert rep["deadlocks"] == 0
    assert rep["hierarchy_violations"] == 0
    assert rep["trace_orphans"] == 0
    assert rep["sampler"]["dealt_dead_tickets"] == 0
    assert rep["consumer"]["sample_path_buffer_acqs"] == 0
    assert rep["consumer"]["consumer_kills"] == 1
    assert rep["ingest_shards"] == 1  # coerced: single commit thread
    assert rep["sampler"]["dealt_blocks"] > 0
    assert rep["consumer"]["blocks_consumed"] > 0


# ------------------------------------------------- the --sampler rule


@pytest.mark.parametrize("flag,backend,want", [
    ("auto", None, "host"),   # off a TPU (tier-1 runs on the CPU)
    ("auto", "tpu", "scan"),
    ("scan", None, "scan"),
    ("host", None, "host"),
    ("pallas", None, ValueError),
    ("einsum", None, ValueError),
])
def test_resolve_sampler_policy_and_validation(monkeypatch, flag, backend,
                                              want):
    """``auto`` reads the platform and nothing else: no timing pass, so
    nothing compiles; the two arms pass through; anything else is
    refused."""
    from d4pg_tpu.io.profiling import RecompileSentinel
    from d4pg_tpu.replay.device_sampler import resolve_sampler

    if backend is not None:
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is ValueError:
        with pytest.raises(ValueError, match="unknown --sampler arm"):
            resolve_sampler(flag)
        return
    with RecompileSentinel() as compiles:
        assert resolve_sampler(flag) == want
    assert compiles.compilations == 0
