"""Device-resident PER trees + fused chunk step (replay/device_per.py,
learner/fused.py, replay/fused_buffer.py) against the host implementations
as oracle (replay/segment_tree.py mirrors the reference's
prioritized_replay_memory.py:33-162)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay
from d4pg_tpu.replay.segment_tree import MinTree, SumTree
from d4pg_tpu.replay.uniform import TransitionBatch


CAP = 64


def _host_trees(idx, values):
    s, m = SumTree(CAP), MinTree(CAP)
    s.set(idx, values)
    m.set(idx, values)
    return s, m


def test_set_leaves_matches_host_trees(rng):
    idx = rng.choice(CAP, size=40, replace=False)
    vals = rng.integers(1, 100, size=40).astype(np.float64)
    s, m = _host_trees(idx, vals)
    trees = dper.set_leaves(dper.init(CAP), jnp.asarray(idx),
                            jnp.asarray(vals, jnp.float32))
    assert np.isclose(float(trees.sum_tree[1]), s.sum(), rtol=1e-6)
    assert float(trees.min_tree[1]) == m.min()
    got = np.asarray(trees.sum_tree[CAP + idx])
    np.testing.assert_allclose(got, vals, rtol=1e-6)


def test_prefix_sample_matches_host_descent(rng):
    idx = np.arange(CAP)
    vals = rng.integers(1, 50, size=CAP).astype(np.float64)
    s, _ = _host_trees(idx, vals)
    trees = dper.set_leaves(dper.init(CAP), jnp.asarray(idx),
                            jnp.asarray(vals, jnp.float32))
    key = jax.random.key(3)
    B = 32
    got = np.asarray(dper.sample(trees, key, B, jnp.int32(CAP)))
    # replicate the stratified masses with the same uniforms
    u = np.asarray(jax.random.uniform(key, (B,)), np.float64)
    total = float(trees.sum_tree[1])
    mass = (np.arange(B) + u) * (total / B)
    expect = s.find_prefixsum(mass)
    np.testing.assert_array_equal(got, expect)


def test_sample_respects_size_limit(rng):
    # only the first 10 slots are written; samples must stay inside them
    idx = np.arange(10)
    trees = dper.set_leaves(dper.init(CAP), jnp.asarray(idx),
                            jnp.ones(10, jnp.float32))
    got = np.asarray(dper.sample(trees, jax.random.key(0), 64, jnp.int32(10)))
    assert got.min() >= 0 and got.max() < 10


def test_is_weights_matches_host_formula(rng):
    idx = np.arange(CAP)
    vals = rng.uniform(0.1, 5.0, size=CAP)
    trees = dper.set_leaves(dper.init(CAP), jnp.asarray(idx),
                            jnp.asarray(vals, jnp.float32))
    q = rng.choice(CAP, size=16)
    beta, size = 0.7, CAP
    got = np.asarray(dper.is_weights(trees, jnp.asarray(q),
                                     jnp.float32(beta), jnp.int32(size)))
    total = vals.sum()
    p_min = vals.min() / total
    max_w = (p_min * size) ** (-beta)
    expect = ((vals[q] / total * size) ** (-beta)) / max_w
    np.testing.assert_allclose(got, expect, rtol=1e-4)


def test_set_leaves_pads_are_dropped(rng):
    """Entries with idx >= capacity are pads: a mixed batch only writes
    its valid rows, and a pad-only call is a no-op (both trees, all
    levels — the repair chain must not let parked pads alias real
    nodes)."""
    trees = dper.set_leaves(dper.init(CAP), jnp.arange(8),
                            jnp.full(8, 2.0, jnp.float32))
    mixed = dper.set_leaves(
        trees, jnp.asarray([1, CAP, 3, CAP]),
        jnp.asarray([5.0, 99.0, 7.0, 99.0], jnp.float32))
    assert float(mixed.sum_tree[CAP + 1]) == 5.0
    assert float(mixed.sum_tree[CAP + 3]) == 7.0
    assert float(mixed.sum_tree[1]) == 2.0 * 6 + 5.0 + 7.0
    assert float(mixed.min_tree[1]) == 2.0
    pads_only = dper.set_leaves(
        mixed, jnp.full(4, CAP), jnp.full(4, 123.0, jnp.float32))
    np.testing.assert_array_equal(np.asarray(pads_only.sum_tree),
                                  np.asarray(mixed.sum_tree))


def test_set_leaves_traces_at_production_capacity():
    """The pad sentinel must not overflow int32 at real buffer sizes
    (1M-slot ring -> tree capacity 2^20): trace-only check."""
    cap = 1 << 20
    out = jax.eval_shape(
        dper.set_leaves, _abstract_trees(cap),
        jax.ShapeDtypeStruct((256,), jnp.int32),
        jax.ShapeDtypeStruct((256,), jnp.float32))
    assert out.sum_tree.shape == (2 * cap,)


def _oracle_init(cap):
    """The oracle's trees: both of ``2 * cap`` nodes, every level and the
    min tree's leaves among them."""
    return dper.init(cap)._replace(
        min_tree=jnp.full(2 * cap, jnp.inf, jnp.float32))


def _level_by_level(trees, idx, p_alpha):
    """The ORACLE: the repair ``set_leaves`` did before the upper levels
    went dense (PR 29), kept here verbatim but for the empty slot (a min
    tree with leaves of its own, each the sum tree's or, where that is no
    priority, ``inf``). After the leaf scatter every level is a gather of
    two children and a scatter of the touched parents, so only the B
    paths are ever written."""
    cap = trees.capacity
    idx32 = idx.astype(jnp.int32)
    valid = idx32 < cap
    node = jnp.where(valid, idx32 + cap, 2 * cap)
    s = trees.sum_tree.at[node].set(p_alpha.astype(jnp.float32),
                                    mode="drop")
    leaf = s[jnp.minimum(node, 2 * cap - 1)]
    m = trees.min_tree.at[node].set(jnp.where(leaf > 0, leaf, jnp.inf),
                                    mode="drop")
    for _ in range(int(math.log2(cap))):
        node = jnp.where(valid, node >> 1, 2 * cap)
        left = jnp.minimum(node << 1, 2 * cap - 2)
        s = s.at[node].set(s[left] + s[left | 1], mode="drop")
        m = m.at[node].set(jnp.minimum(m[left], m[left | 1]), mode="drop")
    return dper.PerTrees(s, m, trees.max_priority)


_ORACLE_JIT = jax.jit(_level_by_level)
_NEW_JIT = jax.jit(dper.set_leaves)


def _seeded_trees(cap, rng):
    """A consistent tree with history, as ``set_leaves`` and as the oracle
    leave it: three quarters of the ring written in one call (the rest
    still 0 / inf), max_priority off its initial 1."""
    n = max(1, 3 * cap // 4)
    p = jnp.asarray(rng.uniform(0.01, 5.0, n), jnp.float32)
    trees, oracle = (
        fn(init(cap), jnp.arange(n), p)._replace(
            max_priority=jnp.float32(3.25))
        for fn, init in ((_NEW_JIT, dper.init), (_ORACLE_JIT, _oracle_init)))
    _assert_same_trees(trees, oracle)
    return trees, oracle


def _batch(kind, cap, rng):
    """(idx, p_alpha) of one ``set_leaves`` call of the named kind."""
    if kind == "one":
        idx = rng.integers(0, cap, 1)
    elif kind == "random256":  # duplicates certain below 256 leaves
        idx = rng.integers(0, cap, 256)
    elif kind == "wrapping_block":  # the commit's contiguous run, wrapped
        n = min(cap // 2, 4096)
        idx = (cap - n // 3 + np.arange(n)) % cap
    elif kind == "with_pads":
        idx = rng.integers(0, cap, 64)
        idx[rng.random(64) < 0.4] = cap
        idx[-1] = cap + 7  # any index past the ring is a pad
    elif kind == "pads_only":
        idx = np.full(32, cap)
    else:
        raise ValueError(kind)
    # every duplicate of a slot carries one value: XLA leaves the winner
    # among duplicates unspecified, and the two repairs are two programs
    value = rng.uniform(0.01, 5.0, 2 * cap + 8).astype(np.float32)
    return jnp.asarray(idx, jnp.int32), jnp.asarray(value[idx])


def _kept(levels, nodes):
    """Mask over a tree's ``nodes`` nodes: those of the kept ``levels``."""
    mask = np.zeros(nodes, bool)
    for level in levels:
        mask[1 << level:2 << level] = True
    return mask


def _assert_same_trees(got, want):
    """The kept levels of both trees (node 1 among them; the sum tree's
    leaves; the min tree has none and ends above them) and the running
    max are ``want``'s to the bit, ``want`` an oracle's trees of
    ``2 * cap`` nodes with every level written; every other node of
    ``got`` still holds what ``init`` gave it: it was never written."""
    cap, fresh = got.capacity, dper.init(got.capacity)
    assert got.min_tree.shape == (dper.min_tree_nodes(cap),)
    assert want.min_tree.shape == want.sum_tree.shape == (2 * cap,)
    for name, levels in (("sum_tree", dper.kept_levels(cap)),
                         ("min_tree", dper.min_kept_levels(cap)),
                         ("max_priority", None)):
        g, w = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert g.dtype == w.dtype, name
        if g.ndim:  # a tree: every other node is as `init` left it
            kept = _kept(levels, g.size)
            assert kept[1] and not kept[0] and levels[0] == 0
            w = np.where(kept, w[:g.size], np.asarray(getattr(fresh, name)))
        np.testing.assert_array_equal(g.view(np.uint32), w.view(np.uint32),
                                      err_msg=name)


BATCH_KINDS = ("one", "random256", "wrapping_block", "with_pads",
               "pads_only")


@pytest.mark.parametrize("jit", (False, True), ids=("eager", "jit"))
@pytest.mark.parametrize("kind", BATCH_KINDS)
@pytest.mark.parametrize("cap", (16, 256, 4096, 65536))
def test_set_leaves_is_bitwise_the_level_by_level_repair(cap, kind, jit, rng):
    trees, oracle_trees = _seeded_trees(cap, rng)
    idx, p = _batch(kind, cap, rng)
    new, oracle = ((_NEW_JIT, _ORACLE_JIT) if jit
                   else (dper.set_leaves, _level_by_level))
    got, want = new(trees, idx, p), oracle(oracle_trees, idx, p)
    _assert_same_trees(got, want)
    if kind == "pads_only":  # changes nothing
        _assert_same_trees(got, oracle_trees)


def _rows_above(batch):
    """The narrowest level a step by rows starts from at ``batch`` leaves,
    said apart from ``repair_plan``: the first more than
    ``_WHOLE_NODES_PER_LEAF * batch`` nodes wide."""
    return (dper._WHOLE_NODES_PER_LEAF * batch).bit_length()


@pytest.mark.parametrize("batch", (1, 4, 48, 256, 512, 4096, "capacity"))
def test_repair_plan_either_side_of_each_change_of_form(batch, rng):
    """A step is by rows where the level below is more than 4,096 nodes a
    leaf wide, whole where it is narrower, and whole under the root: at
    the capacity where the leaves' step changes form and a level either
    side of it (B 1: 2^12 whole, 2^13 rows; 4: 2^15; 48: 2^18; 256:
    2^21, the MLP cells' chunk; 512: 2^22; 4,096: 2^25; a batch of every
    leaf: never), every step above follows the same rule, and up to 2^18
    leaves the trees are the oracle's through two calls."""
    turns = 22 if batch == "capacity" else _rows_above(batch)
    for levels in (turns - 1, turns, turns + 1):
        cap = 1 << levels
        b = cap if batch == "capacity" else batch
        plan = dper.repair_plan(cap, b)
        kept = dper.kept_levels(cap)
        assert [s[0] for s in plan] == list(kept[:0:-1])
        assert [s[1] for s in plan] == list(kept[-2::-1])
        for below, above, form in plan:
            rows = above > 0 and below >= _rows_above(b)
            assert form == ("rows" if rows else "whole"), (cap, below)
        assert (plan[0][2] == "rows") == (batch != "capacity"
                                          and levels >= turns)
        assert plan[-1][1:] == (0, "whole")
        if levels > 18:
            continue
        trees, oracle_trees = _seeded_trees(cap, rng)
        for _ in range(2):  # the second call starts from the first's trees
            idx = jnp.asarray(rng.integers(0, cap, b), jnp.int32)
            p = jnp.asarray(rng.uniform(0.01, 5.0, 2 * cap), jnp.float32)[idx]
            trees = _NEW_JIT(trees, idx, p)
            oracle_trees = _ORACLE_JIT(oracle_trees, idx, p)
            _assert_same_trees(trees, oracle_trees)


@pytest.mark.parametrize("levels, kept", [
    (0, (0,)), (1, (0, 1)), (6, (0, 6)), (7, (0, 7)), (8, (0, 1, 8)),
    (14, (0, 7, 14)), (15, (0, 1, 8, 15)), (16, (0, 2, 9, 16)),
    (21, (0, 7, 14, 21)), (22, (0, 1, 8, 15, 22))])
def test_kept_levels_are_every_seventh_from_the_leaves_and_the_root(levels,
                                                                    kept):
    assert dper.kept_levels(1 << levels) == kept


# which steps go by rows in a 4,096-leaf tree (kept levels 0, 5, 12) at
# B = 4 / 256 / 2,048 under each value of the rule's constant
FORCED = {"rows": 0, "whole": 1 << 30, "rows_under_256": 16}


@pytest.mark.parametrize("form", FORCED)
def test_set_leaves_rows_and_whole_forced_in_turn(form, rng, monkeypatch):
    """With the rule's constant turned a 4,096-leaf tree takes its 12 > 5
    step by rows at every batch, whole at every batch, and by rows under
    256 leaves only; through a sequence of inserts, duplicate-laden
    updates and padded batches each must leave the oracle's trees."""
    monkeypatch.setattr(dper, "_WHOLE_NODES_PER_LEAF", FORCED[form])
    cap = 4096
    assert [dper.repair_plan(cap, b)[0][2] for b in (4, 256, 2048)] == {
        "rows": ["rows"] * 3, "whole": ["whole"] * 3,
        "rows_under_256": ["rows", "whole", "whole"]}[form]
    assert dper.repair_plan(cap, 4)[1] == (5, 0, "whole")
    new = jax.jit(dper.set_leaves)  # traced under this constant
    got, want = dper.init(cap), _oracle_init(cap)
    for step in range(6):
        kind = ("wrapping_block", "random256", "with_pads")[step % 3]
        idx, p = _batch(kind, cap, rng)
        idx, p = (idx[:4], p[:4]) if step == 5 else (idx, p)
        got, want = new(got, idx, p), _ORACLE_JIT(want, idx, p)
        _assert_same_trees(got, want)


def _pairwise(level, op, rounds):
    """numpy: ``rounds`` rounds of adjacent pairs over a level."""
    for _ in range(rounds):
        level = op(level[0::2], level[1::2])
    return level


def _assert_the_invariant(trees):
    """THE INVARIANT, rebuilt in numpy from the sum tree's leaves alone:
    every kept level of the sum tree is the kept level below it totalled
    by rounds of adjacent float32 pairs; every kept level of the min tree
    (which has no leaves) is the rounds of adjacent pairs over
    ``where(leaf > 0, leaf, inf)`` of the SUM tree's leaves; every other
    node of both is as ``init`` left it."""
    cap = trees.capacity
    s, m = np.asarray(trees.sum_tree), np.asarray(trees.min_tree)
    levels = int(math.log2(cap))
    want_s = np.zeros(2 * cap, np.float32)
    want_s[cap:] = s[cap:]
    kept = dper.kept_levels(cap)
    for above, below in zip(kept[-2::-1], kept[::-1]):
        want_s[1 << above:2 << above] = _pairwise(
            want_s[1 << below:2 << below], np.add, below - above)
    side = np.where(s[cap:] > 0, s[cap:], np.float32(np.inf))
    want_m = np.full(dper.min_tree_nodes(cap), np.inf, np.float32)
    for level in dper.min_kept_levels(cap):
        want_m[1 << level:2 << level] = _pairwise(side, np.minimum,
                                                  levels - level)
    for got, want in ((s, want_s), (m, want_m)):
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))


MIN_TREE_CAPS = (8, 16, 128, 1024, 65536)


@pytest.mark.parametrize("form", ("rows", "whole"))
@pytest.mark.parametrize("batch", (1, 256, 4096, "capacity"))
@pytest.mark.parametrize("cap", MIN_TREE_CAPS)
def test_min_root_is_the_host_min_trees_over_the_written_leaves(
        cap, batch, form, rng, monkeypatch):
    """The min tree has no leaves, and its root is still the host
    ``MinTree``'s over the leaves ever written, to the bit, through a
    sequence of ``insert`` (a wrapping block), ``set_leaves`` (duplicates
    with DIFFERENT values: whichever write wins, both trees see that
    winner, the one leaf there is) and ``update_from_td`` (pads among the
    indices) on a ring three quarters in use, at B of 1 / 256 / 4,096 /
    every leaf, with every step that can go by rows forced by rows and
    then whole (the rule's constant turned, as ``FORCED`` does); after
    every call both trees are the invariant's over the sum tree's
    leaves."""
    monkeypatch.setattr(dper, "_WHOLE_NODES_PER_LEAF", FORCED[form])
    b = cap if batch == "capacity" else batch
    if cap > 128:
        assert dper.repair_plan(cap, b)[0][2] == form
    # fresh function objects: traced under this constant, not an earlier
    set_leaves = jax.jit(lambda t, i, p: dper.set_leaves(t, i, p))
    insert = jax.jit(lambda t, i: dper.insert(t, i, 0.6))
    update = jax.jit(lambda t, i, td: dper.update_from_td(t, i, td, 0.6))
    live = max(1, 3 * cap // 4)
    trees, host, written = dper.init(cap), MinTree(cap), np.zeros(cap, bool)
    assert float(trees.min_tree[1]) == host.min() == np.inf
    for call in range(6):
        if call % 3 == 0:
            idx = (live - b // 3 + np.arange(b)) % live
            trees = insert(trees, jnp.asarray(idx, jnp.int32))
        elif call % 3 == 1:
            idx = rng.integers(0, min(live, 64), b)
            p = rng.uniform(0.01, 5.0, b).astype(np.float32)
            trees = set_leaves(trees, jnp.asarray(idx, jnp.int32),
                               jnp.asarray(p))
        else:
            idx = rng.integers(0, live, b)
            if b > 1:
                idx[rng.random(b) < 0.3] = cap  # pads
            td = rng.normal(0.0, 3.0, b).astype(np.float32)
            trees = update(trees, jnp.asarray(idx, jnp.int32),
                           jnp.asarray(td))
        written[idx[idx < cap]] = True
        slots = np.flatnonzero(written)
        leaves = np.asarray(trees.sum_tree[cap:])
        assert (leaves[slots] > 0).all() and not leaves[~written].any()
        host.set(slots, leaves[slots])
        root = np.asarray(trees.min_tree[1])
        assert root.dtype == np.float32 and float(root) == host.min()
        _assert_the_invariant(trees)


@pytest.mark.parametrize("cap, batch, form", [
    (1, 1, None), (8, 4, None), (128, 4, None), (4096, 4, "rows"),
    (4096, 4, "whole"), (4096, 4096, None), (1 << 15, 4, None)])
def test_a_written_zero_is_an_empty_slot_in_both_trees(cap, batch, form,
                                                       rng, monkeypatch):
    """A sum-tree leaf of exactly 0 adds no mass, cannot be sampled and is
    left out of the minimum, written or never written alike (``init``'s
    0 / inf made a rule): zeros written over the smallest priorities leave
    the min root at the smallest POSITIVE leaf and the importance weights
    finite, where a min tree with leaves of its own read 0 and made every
    weight 0 or NaN; a ring of nothing but zeros is a fresh one."""
    if form is not None:
        monkeypatch.setattr(dper, "_WHOLE_NODES_PER_LEAF", FORCED[form])
    set_leaves = jax.jit(lambda t, i, p: dper.set_leaves(t, i, p))
    vals = rng.uniform(0.5, 5.0, cap).astype(np.float32)
    trees = set_leaves(dper.init(cap), jnp.arange(cap), jnp.asarray(vals))
    assert float(trees.min_tree[1]) == vals.min()
    zeroed = np.argsort(vals)[:min(batch, cap // 2)]  # the smallest
    idx = np.resize(zeroed, batch) if zeroed.size else np.full(batch, cap)
    trees = set_leaves(trees, jnp.asarray(idx, jnp.int32),
                       jnp.zeros(batch, jnp.float32))
    vals[zeroed] = 0.0
    np.testing.assert_array_equal(np.asarray(trees.sum_tree[cap:]), vals)
    _assert_the_invariant(trees)
    assert float(trees.min_tree[1]) == vals[vals > 0].min()
    got = np.asarray(dper.sample(trees, jax.random.key(0), 64,
                                 jnp.int32(cap)))
    assert (vals[got] > 0).all()
    w = np.asarray(dper.is_weights(trees, jnp.asarray(got),
                                   jnp.float32(0.5), jnp.int32(cap)))
    assert np.isfinite(w).all() and (w > 0).all() and w.max() <= 1.0
    emptied = set_leaves(trees, jnp.arange(cap), jnp.zeros(cap, jnp.float32))
    fresh = dper.init(cap)
    for name in ("sum_tree", "min_tree"):
        np.testing.assert_array_equal(np.asarray(getattr(emptied, name)),
                                      np.asarray(getattr(fresh, name)))


def _abstract_trees(cap):
    return jax.eval_shape(lambda: dper.init(cap))


def _lowered(cap, batch):
    """What ``set_leaves`` lowers to (trace and lower only): ``(scatters,
    gathers of 128-node rows, [nodes a tree each reduce_window reads])``,
    both trees in every window. ONE scatter goes into a ``2 * cap``
    operand, the sum tree's leaf scatter; every other goes into a kept
    level's own slice. A row gather reads the sum tree, or the (smaller)
    min tree at a step by rows above the first; none reads the sum tree
    twice at a step."""
    import re

    text = jax.jit(dper.set_leaves).lower(
        _abstract_trees(cap), jax.ShapeDtypeStruct((batch,), jnp.int32),
        jax.ShapeDtypeStruct((batch,), jnp.float32)).as_text()
    windows = [int(r) * int(w) for r, w in re.findall(
        r"\}\) : \(tensor<(\d+)x(\d+)xf32>, tensor<\d+x\d+xf32>, "
        r"tensor<f32>, tensor<f32>\) ->", text)]
    assert len(windows) == text.count('"stablehlo.reduce_window"(')
    into = [int(n) for n in re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<(\d+)xf32>', text, re.S)]
    assert len(into) == text.count('"stablehlo.scatter"(')
    assert into.count(2 * cap) == 1 and max(into) == 2 * cap
    gathers = [ln for ln in text.splitlines() if '"stablehlo.gather"(' in ln]
    rows = [ln for ln in gathers
            if "slice_sizes = array<i64: 1, 128>" in ln]
    assert len(rows) == len(gathers)  # no leaf is read back by the scalar
    of_sum = [f"(tensor<{2 * cap // 128}x128xf32>," in ln for ln in rows]
    of_min = [f"(tensor<{dper.min_tree_nodes(cap) // 128}x128xf32>," in ln
              for ln in rows]
    assert all(s or m for s, m in zip(of_sum, of_min))
    by_rows = [form for _b, _a, form
               in dper.repair_plan(cap, batch)].count("rows")
    assert of_sum.count(True) == by_rows
    return len(into), len(rows), windows


def _windows_of(plan):
    """The nodes a tree each ``reduce_window`` of a plan reads: a window a
    round of every whole step."""
    return sorted(((1 << below) >> i for below, above, form in plan
                   if form == "whole" for i in range(below - above)),
                  reverse=True)


@pytest.mark.parametrize("grow", (0, 1, 2), ids=("2M", "4M", "8M"))
@pytest.mark.parametrize("batch", (256, 4096))
def test_set_leaves_structure_at_production_capacity(batch, grow):
    """The structure of the repair at the MLP cells' 2,097,152 leaves and
    at rings twice and four times the size, read off the lowered module.
    The chunk's B = 256: ONE scatter into a tree-sized operand (the sum
    tree's leaves; the parent had a second, of the same values at the
    same nodes of the min tree, and a gather of the leaves between them),
    ONE gather of 256 rows of 128 leaves that both trees total (the
    parent had one a tree), two scatters of the totals (into the level's
    own slice), and fourteen windows (2^21) of which the widest reads the
    first kept level above the leaves and none the leaves. The commit's
    4,096: the one leaf scatter and a window a level, none of which reads
    more than one kept level's span; the stream over the leaves reads the
    sum tree's alone."""
    cap = 1 << (21 + grow)
    scatters, rows, windows = _lowered(cap, batch)
    plan = dper.repair_plan(cap, batch)
    by_rows = [form for _b, _a, form in plan].count("rows")
    assert by_rows == (1 if batch == 256 else 0)
    assert (scatters, rows) == (1 + 2 * by_rows, by_rows)
    assert sorted(windows, reverse=True) == _windows_of(plan)
    assert len(windows) == 21 + grow - 7 * by_rows
    assert max(windows) == cap >> 7 * by_rows


@pytest.mark.parametrize("levels, batch, scatters, rows, windows", [
    (21, 1, 1 + 4, 3, [128 >> i for i in range(7)]),
    (21, 64, 1 + 2, 1, None),
    (16, 512, 1, 0, [1 << 16 >> i for i in range(16)]),
    (15, 4, 1 + 2, 1, None),  # cells 4 and 6
    (21, 1 << 21, 1, 0, None),
], ids=("per_row_insert", "64_on_2M", "pixel_cell", "torso_cells",
        "every_leaf"))
def test_set_leaves_structure_follows_the_batch(levels, batch, scatters,
                                                rows, windows):
    """Few leaves on a large tree go by rows further up: a per-row insert
    (``drain_per_row``) into the 2M-leaf ring gathers a row of the sum
    tree at the first step (both trees total it) and a row a tree at the
    second (three gathers, a scatter a tree and step into the levels' own
    slices) and runs the seven windows under the root only; the pixel
    cell's 512 leaves on 65,536 and a batch of every leaf are whole at
    every step: the leaf scatter and nothing else."""
    got = _lowered(1 << levels, batch)
    assert got[:2] == (scatters, rows)
    assert windows is None or got[2] == windows


# the benchmark's seven cells: what the chunk's write-back and the commit's
# insert do to the trees at the shapes of each cell's configuration file
CELL_PLANS = {
    "humanoid-mlp.learn-static": (
        "21>14 rows(min from sum),14>7 whole,7>root whole",
        "21>14 whole(min from sum),14>7 whole,7>root whole"),
    "dmc-pixels-drq.learn-static": (
        "16>9 whole(min from sum),9>2 whole,2>root whole",
        "16>9 whole(min from sum),9>2 whole,2>root whole"),
    "humanoid-mlp.learn-ingest": (
        "21>14 rows(min from sum),14>7 whole,7>root whole",
        "21>14 whole(min from sum),14>7 whole,7>root whole"),
    "humanoid-mellum2-ep4.learn-static": (
        "15>8 rows(min from sum),8>1 whole,1>root whole",
        "15>8 whole(min from sum),8>1 whole,1>root whole"),
    "humanoid-keye2-ep8.learn-static": (
        "14>7 rows(min from sum),7>root whole",
        "14>7 whole(min from sum),7>root whole"),
    "humanoid-lfm2-ep4.learn-static": (
        "15>8 rows(min from sum),8>1 whole,1>root whole",
        "15>8 whole(min from sum),8>1 whole,1>root whole"),
    "humanoid-qwen3next-ep32.learn-static": (
        "14>7 rows(min from sum),7>root whole",
        "14>7 whole(min from sum),7>root whole"),
    "humanoid-ouro-ut4.learn-static": (
        "15>8 rows(min from sum),8>1 whole,1>root whole",
        "15>8 whole(min from sum),8>1 whole,1>root whole"),
    "humanoid-nemotronh-ep16.learn-static": (
        "15>8 rows(min from sum),8>1 whole,1>root whole",
        "15>8 whole(min from sum),8>1 whole,1>root whole"),
    "humanoid-trinity-ep16.learn-static": (
        "14>7 rows(min from sum),7>root whole",
        "14>7 whole(min from sum),7>root whole"),
}


@pytest.mark.parametrize("cell", CELL_PLANS)
def test_repair_plan_at_the_benchmarks_cells(cell):
    """``repair_plan`` (as ``plan_text`` spells it for ``train``'s
    ``plan:`` line) at each cell's ``(capacity, B)``: the chunk's batch
    and the commit's block. Only the MLP cells' 2^21-leaf tree is wide
    enough for 256 leaves to go by rows; the torso cells' batches of 2
    and 4 do on their small trees; every commit is whole."""
    import json
    import os

    from d4pg_tpu.replay.segment_tree import next_pow2

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = {w["name"]: w["config"] for w in json.load(f)["workloads"]}
    assert sorted(cells) == sorted(CELL_PLANS)
    with open(os.path.join(root, "benchmark/configs",
                           cells[cell] + ".json")) as f:
        cfg = json.load(f)
    cap = next_pow2(cfg["replay"]["capacity"])
    chunk, commit = CELL_PLANS[cell]
    assert dper.plan_text(cap, cfg["learner"]["batch_size"]) == chunk
    assert dper.plan_text(cap, cfg["replay"]["block_rows"]) == commit


def _walk_level_by_level(sum_tree, mass):
    """The ORACLE: the descent ``descend`` was before it read the tree a
    row at a time (PR 35), kept here verbatim: one gather of a scalar a
    query at every level, at the node the level above chose."""
    cap = sum_tree.shape[0] // 2
    p = mass
    node = jnp.ones(mass.shape, jnp.int32)
    for _ in range(int(math.log2(cap))):
        left = node << 1
        left_sum = sum_tree[left]
        go_right = p >= left_sum
        p = jnp.where(go_right, p - left_sum, p)
        node = jnp.where(go_right, left | 1, left)
    return node - cap


_WALK_ORACLE_JIT = jax.jit(_walk_level_by_level)
_DESCEND_JIT = jax.jit(dper.descend)
# every log2(capacity) mod 7 from 0 to 6: 1, 4, 6, 0, 1, 5, 0, 1, 2, 0
DESCEND_CAPS = (2, 16, 64, 128, 256, 4096, 1 << 14, 1 << 15, 1 << 16,
                1 << 21)


def _priorities(cap, rng, zero_share=0.1):
    vals = rng.uniform(0.01, 5.0, cap).astype(np.float32)
    vals[rng.random(cap) < zero_share] = 0.0
    return vals


def _full_trees(vals):
    """``(trees, oracle)`` over the leaves ``vals``: as ``set_leaves``
    leaves them (kept levels only: what ``descend`` gets) and as the
    level-by-level repair does (every level: what the level-by-level
    walk needs)."""
    trees, oracle = (fn(init(vals.size), jnp.arange(vals.size),
                        jnp.asarray(vals))
                     for fn, init in ((_NEW_JIT, dper.init),
                                      (_ORACLE_JIT, _oracle_init)))
    _assert_same_trees(trees, oracle)
    return trees, oracle


def _masses(sum_tree, shape, rng):
    u = jnp.asarray(rng.random(shape), jnp.float32)
    return dper.strata_mass(u, sum_tree[1])


def _assert_same_slots(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("jit", (False, True), ids=("eager", "jit"))
@pytest.mark.parametrize("blocks", (None, 3), ids=("B", "blocksxB"))
@pytest.mark.parametrize("cap", DESCEND_CAPS)
def test_descend_is_bitwise_the_level_by_level_walk(cap, blocks, jit, rng):
    """The slots of the row-at-a-time walk are those of (a) the
    level-by-level walk and (b) the float32 host twin's
    ``find_prefixsum`` (``ShardSlicePerTrees``, bitwise
    ``SumTree.find_prefixsum`` over float32 nodes), with a tenth of the
    leaves zero, for ``mass`` of shape [B] and [blocks, B]."""
    from d4pg_tpu.replay.sampler import ShardSlicePerTrees

    vals = _priorities(cap, rng)
    trees, every_level = _full_trees(vals)
    batch = min(64, 2 * cap)
    mass = _masses(trees.sum_tree,
                   (batch,) if blocks is None else (blocks, batch), rng)
    new, oracle = ((_DESCEND_JIT, _WALK_ORACLE_JIT) if jit
                   else (dper.descend, _walk_level_by_level))
    got = new(trees.sum_tree, mass)
    assert got.dtype == jnp.int32
    _assert_same_slots(got, oracle(every_level.sum_tree, mass))
    twin = ShardSlicePerTrees(cap, 1, dtype=np.float32)
    twin.set(np.arange(cap), vals)
    assert np.float32(twin.total()) == np.asarray(trees.sum_tree[1])
    _assert_same_slots(got, twin.find_prefixsum(np.asarray(mass))
                       .astype(np.int32))


@pytest.mark.parametrize("cap", (16, 256, 1 << 15, 1 << 16))
def test_descend_under_vmap_over_a_shard_axis(cap, rng):
    """The sharded chunk's shape: trees stacked on a leading shard axis,
    each shard's queries through its own tree."""
    shards = [_full_trees(_priorities(cap, rng)) for _ in range(3)]
    stacked = jnp.stack([trees.sum_tree for trees, _oracle in shards])
    mass = jnp.stack([_masses(t, (32,), rng) for t in stacked])
    got = jax.jit(jax.vmap(dper.descend))(stacked, mass)
    want = jnp.stack([_WALK_ORACLE_JIT(oracle.sum_tree, m)
                      for (_trees, oracle), m in zip(shards, mass)])
    _assert_same_slots(got, want)


@pytest.mark.parametrize("depth", range(1, 15))
def test_descend_goes_right_on_a_tie_at_every_level(depth, rng):
    """A query EQUAL to a left subtree's sum descends right (the TIE RULE),
    at each of the seven levels of the slice under the root and of the one
    gathered row of a 2^14-leaf tree. Integer priorities keep every sum
    exact: over all-ones leaves the mass ``2^(14 - depth)`` meets its tie
    ``depth`` levels down and lands on leaf ``2^(14 - depth)``; over
    random small integers every prefix sum is a tie somewhere, and lands
    on the next leaf that has any mass."""
    cap = 1 << 14
    ones = _full_trees(np.ones(cap, np.float32))[0].sum_tree
    tie = jnp.full((8,), float(cap >> depth), jnp.float32)
    _assert_same_slots(_DESCEND_JIT(ones, tie),
                       np.full(8, cap >> depth, np.int32))
    vals = rng.integers(0, 4, cap).astype(np.float32)
    vals[-1] = 1.0
    tree, every_level = (x.sum_tree for x in _full_trees(vals))
    prefix = np.cumsum(vals, dtype=np.float64)
    # ties whose deciding node is `depth` levels down: the prefix sums
    # at the right edge of each left subtree at that depth
    edges = np.arange(cap >> depth, cap, cap >> (depth - 1))[:64] - 1
    mass = jnp.asarray(prefix[edges], jnp.float32)
    got = _DESCEND_JIT(tree, mass)
    _assert_same_slots(got, _WALK_ORACLE_JIT(every_level, mass))
    _assert_same_slots(got, np.searchsorted(prefix, prefix[edges],
                                            side="right").astype(np.int32))
    assert (np.asarray(got) > edges).all()  # never the left subtree


@pytest.mark.parametrize("cap", (16, 128, 4096, 1 << 15))
def test_descend_past_the_total_lands_on_the_last_leaf(cap, rng):
    """``mass >= total`` goes right at every node, onto the last leaf;
    ``sample_from_uniforms`` then clips it onto the written ones."""
    vals = rng.integers(1, 4, cap).astype(np.float32)
    trees, every_level = _full_trees(vals)
    total = trees.sum_tree[1]
    mass = jnp.stack([total, 2 * total, jnp.float32(jnp.inf)])
    got = _DESCEND_JIT(trees.sum_tree, mass)
    _assert_same_slots(got, _WALK_ORACLE_JIT(every_level.sum_tree, mass))
    _assert_same_slots(got, np.full(3, cap - 1, np.int32))
    limit = jnp.int32(cap // 2)
    u = jnp.full((8,), np.nextafter(np.float32(1), np.float32(0)))
    idx = np.asarray(dper.sample_from_uniforms(trees, u, limit))
    assert idx.max() == cap // 2 - 1 and idx.min() >= 0


@pytest.mark.parametrize("form", ("rows", "whole", None),
                         ids=("rows", "whole", "the_rules_own"))
def test_descend_after_fifty_set_leaves_and_inserts(form, rng, monkeypatch):
    """The pairwise rebuild inside a row relies on ``set_leaves``'s
    invariant; it holds after fifty random ``set_leaves`` / ``insert``
    calls through the repair by rows and the whole one alike (the rule's
    constant turned as in the forced test above; ``None`` leaves it, and
    a 4,096-leaf tree then goes whole)."""
    cap = 4096
    if form is not None:
        monkeypatch.setattr(dper, "_WHOLE_NODES_PER_LEAF", FORCED[form])
    assert dper.repair_plan(cap, 4)[0] == (12, 5, form or "whole")
    set_leaves = jax.jit(dper.set_leaves)  # traced under this constant
    insert = jax.jit(dper.insert, static_argnames=("alpha",))
    trees = dper.init(cap)
    for call in range(50):
        idx = jnp.asarray(rng.integers(0, cap, 4), jnp.int32)
        if call % 2:
            trees = insert(trees, idx, alpha=0.6)
        else:
            p = rng.uniform(0.0, 5.0, 4) * (rng.random(4) > 0.1)
            trees = set_leaves(trees, idx, jnp.asarray(p, jnp.float32))
            trees = trees._replace(max_priority=jnp.float32(1 + call))
        if call % 7 == 0 or call == 49:
            mass = _masses(trees.sum_tree, (64,), rng)
            # the level-by-level walk's tree: every level, over the leaves
            # the fifty calls have left
            every_level = _ORACLE_JIT(_oracle_init(cap), jnp.arange(cap),
                                      trees.sum_tree[cap:])
            _assert_same_slots(_DESCEND_JIT(trees.sum_tree, mass),
                               _WALK_ORACLE_JIT(every_level.sum_tree, mass))


def _sample_and_weigh(descend, batch):
    """``sample`` + ``is_weights`` as the chunk calls them, over a given
    descent."""
    def fn(trees, key, size):
        u = jax.random.uniform(key, (batch,))
        idx = descend(trees.sum_tree,
                      dper.strata_mass(u, trees.sum_tree[1]))
        idx = jnp.minimum(idx, jnp.maximum(size - 1, 0))
        return idx, dper.is_weights(trees, idx, jnp.float32(0.5), size)
    return fn


def _tree_gathers(fn, cap):
    """(row gathers, scalar gathers) out of a ``2 * cap``-node tree in
    ``fn``'s lowered module (trace and lower only)."""
    text = jax.jit(fn).lower(
        _abstract_trees(cap),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype),
        jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    gathers = [ln for ln in text.splitlines() if '"stablehlo.gather"(' in ln]
    rows = [ln for ln in gathers
            if f"(tensor<{2 * cap // 128}x128xf32>," in ln]
    scalars = [ln for ln in gathers if f"(tensor<{2 * cap}xf32>," in ln]
    assert len(rows) + len(scalars) == len(gathers)
    assert all("slice_sizes = array<i64: 1, 128>" in ln for ln in rows)
    assert all("slice_sizes = array<i64: 1>" in ln for ln in scalars)
    return len(rows), len(scalars)


@pytest.mark.parametrize("levels, batch", [(21, 256), (16, 512)],
                         ids=("mlp_cells", "pixel_cell"))
def test_sample_gathers_rows_not_scalars_at_production_capacity(levels,
                                                                batch):
    """The structure of the walk, read off the lowered module: at the
    benchmark's shapes two gathers of B rows of 128 nodes (21 = 7 static
    + 2 x 7; 16 = 2 static + 2 x 7) and the one scalar gather of
    ``is_weights``'s leaves, where the level-by-level walk has a scalar
    gather a level and that one."""
    cap = 1 << levels
    assert _tree_gathers(_sample_and_weigh(dper.descend, batch), cap) \
        == (2, 1)
    assert _tree_gathers(_sample_and_weigh(_walk_level_by_level, batch),
                         cap) == (0, levels + 1)
    # what dper.sample itself lowers to is the walk counted above
    direct = lambda t, k, n: (  # noqa: E731
        dper.sample(t, k, batch, n), jnp.float32(0))
    assert _tree_gathers(direct, cap) == (2, 0)


@pytest.mark.parametrize("levels, rows", [(1, 0), (6, 0), (7, 0), (8, 1),
                                          (14, 1), (15, 2), (22, 3)])
def test_descend_row_gathers_follow_the_capacity(levels, rows):
    """``log2(capacity) // 7`` row gathers, one fewer where the levels
    divide by seven (the first row is then the static slice); none under
    256 leaves."""
    cap = 1 << levels
    text = jax.jit(dper.descend).lower(
        jax.ShapeDtypeStruct((2 * cap,), jnp.float32),
        jax.ShapeDtypeStruct((32,), jnp.float32)).as_text()
    assert text.count('"stablehlo.gather"(') == rows
    assert rows == (levels - 1) // 7


@pytest.mark.parametrize("levels, batch", [(21, 256), (16, 512)],
                         ids=("mlp_cells", "pixel_cell"))
def test_the_scan_body_never_copies_the_tree_for_its_row_view(levels, batch):
    """``[2N] -> [2N / 128, 128]`` is the same bytes. Compiled (here for
    the CPU; ``tests/test_torso_v5e_compile.py`` asks the chip's compiler)
    inside a scan over donated, loop-carried trees that samples, weighs
    and writes back, the program makes a whole tree only where the
    write-back does: no transpose or convert of one, and no more
    whole-tree instructions (copies among them: the CPU's compiler
    answers the sum tree's two scatters of a step by rows with one; the
    chip's does not) than the same loop over the level-by-level walk
    has."""
    import re

    cap = 1 << levels

    def whole_tree_outputs(descend):
        def loop(trees, key, size):
            def body(carry, _):
                trees, key = carry
                key, k = jax.random.split(key)
                idx, w = _sample_and_weigh(descend, batch)(trees, k, size)
                return (dper.update_from_td(trees, idx, w, 0.6), key), idx
            return jax.lax.scan(body, (trees, key), None, length=4)

        text = jax.jit(loop, donate_argnums=(0,)).lower(
            _abstract_trees(cap),
            jax.ShapeDtypeStruct((), jax.random.key(0).dtype),
            jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
        made = re.findall(
            r"= f32\[(?:%d|%d,128)\]\S* ([\w\-]+)\(" % (2 * cap,
                                                       2 * cap // 128), text)
        return sorted(op for op in made if op not in (
            "parameter", "get-tuple-element", "bitcast", "reshape"))

    new = whole_tree_outputs(dper.descend)
    old = whole_tree_outputs(_walk_level_by_level)
    assert new and not {"transpose", "convert"} & set(new)
    assert len(new) <= len(old) and new.count("copy") <= old.count("copy")


def test_commit_program_compiles_once_across_block_shapes(rng):
    """One commit program serves a full block, a partial block, an empty
    tick and a block that wraps the ring: ``n`` and ``start`` are traced
    scalars and the tree insert's split follows the static block."""
    from d4pg_tpu.io.profiling import RecompileSentinel

    cap, block = 256, 32
    buf = FusedDeviceReplay(cap, 4, 2, alpha=0.6, block_rows=block)

    def rows(n):
        return TransitionBatch(
            obs=rng.standard_normal((n, 4)).astype(np.float32),
            action=rng.uniform(-1, 1, (n, 2)).astype(np.float32),
            reward=rng.standard_normal(n).astype(np.float32),
            next_obs=rng.standard_normal((n, 4)).astype(np.float32),
            done=np.zeros(n, np.float32),
            discount=np.full(n, 0.99, np.float32))

    buf.add(rows(block))
    assert buf.drain() == block  # warm-up: the one compile
    with RecompileSentinel() as sentinel:
        for n in (block, 5, 0, block):  # full, partial, empty, full
            if n:
                buf.add(rows(n))
            assert buf.stage_block() == n
            assert buf.commit_staged() == n
        while buf.head + block <= cap:  # up to the ring's end
            buf.add(rows(block))
            buf.drain()
        assert cap - block < buf.head < cap
        buf.add(rows(block))  # this block wraps
        assert buf.drain() == block
    assert buf.head < block and buf.size == cap
    assert sentinel.compilations == 0
    want = _ORACLE_JIT(_oracle_init(cap), jnp.arange(cap),
                       jnp.ones(cap, jnp.float32))
    _assert_same_trees(buf.trees, want)


def test_insert_and_update_semantics():
    trees = dper.init(CAP)
    alpha = 0.6
    trees = dper.insert(trees, jnp.arange(8), alpha)
    # new items enter at max_priority ** alpha == 1 (max_priority starts 1)
    np.testing.assert_allclose(np.asarray(trees.sum_tree[CAP:CAP + 8]), 1.0)
    td = jnp.asarray([3.0, -7.0, 0.5, 1.0])
    trees = dper.update_from_td(trees, jnp.asarray([0, 1, 2, 3]), td, alpha)
    expect = (np.abs(np.asarray(td)) + 1e-6) ** alpha
    np.testing.assert_allclose(np.asarray(trees.sum_tree[CAP:CAP + 4]),
                               expect, rtol=1e-5)
    # running max tracks the raw priority, so later inserts inherit it
    assert np.isclose(float(trees.max_priority), 7.0 + 1e-6)
    trees = dper.insert(trees, jnp.asarray([9]), alpha)
    assert np.isclose(float(trees.sum_tree[CAP + 9]),
                      (7.0 + 1e-6) ** alpha, rtol=1e-5)


def test_device_trees_match_host_under_random_op_sequences(rng):
    """Stateful fuzz: a random interleaving of inserts, priority updates
    (with duplicate indices) and prefix-sum queries keeps the device
    trees in lock-step with the host numpy trees (the reference-parity
    oracle). Duplicate-update batches are made value-consistent so the
    unspecified-winner freedom cannot cause a legitimate divergence."""
    s_host, m_host = SumTree(CAP), MinTree(CAP)
    trees = dper.init(CAP)
    live = 0
    for step in range(30):
        if rng.integers(2) == 0 or live == 0:  # insert a block of new slots
            n = int(rng.integers(1, 9))
            idx = (np.arange(live, live + n) % CAP)
            live = min(live + n, CAP)
            p = float(np.asarray(trees.max_priority)) ** 0.6
            s_host.set(idx, np.full(n, p))
            m_host.set(idx, np.full(n, p))
            trees = dper.insert(trees, jnp.asarray(idx), 0.6)
        else:  # priority update with possible duplicates
            n = int(rng.integers(1, 9))
            idx = rng.integers(0, live, size=n)
            vals = rng.uniform(0.5, 4.0, size=len(np.unique(idx)))
            # same value for every duplicate of a slot
            lut = dict(zip(np.unique(idx), vals))
            pr = np.array([lut[i] for i in idx])
            s_host.set(idx, pr**0.6)
            m_host.set(idx, pr**0.6)
            trees = dper.set_leaves(trees, jnp.asarray(idx),
                                    jnp.asarray(pr**0.6, jnp.float32))
        np.testing.assert_allclose(float(trees.sum_tree[1]), s_host.sum(),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(trees.min_tree[1]), m_host.min(),
                                   rtol=1e-6)
        leaf_idx = np.arange(live)
        np.testing.assert_allclose(
            np.asarray(trees.sum_tree[CAP + leaf_idx]),
            s_host.get(leaf_idx), rtol=1e-5)
    # final: a batch of prefix queries descends to the same leaves
    mass = rng.uniform(0, s_host.sum() * 0.999, size=64)
    host_leaves = s_host.find_prefixsum(mass)
    # the device descent on the same masses
    dev_leaves = np.asarray(dper.descend(trees.sum_tree,
                                         jnp.asarray(mass, jnp.float32)))
    # f32 vs f64 partial sums can disagree exactly at a leaf boundary;
    # allow off-by-one-leaf there
    assert (np.abs(dev_leaves - host_leaves) <= 1).all()
    assert (dev_leaves == host_leaves).mean() > 0.9


def test_beta_schedule_matches_host_schedule():
    from d4pg_tpu.replay import LinearSchedule

    host = LinearSchedule(1000, 1.0, 0.4)
    for t in (0, 250, 999, 5000):
        got = float(dper.beta_schedule(jnp.int32(t), 0.4, 1000))
        assert np.isclose(got, host.value(t), atol=1e-6)


def _fill_storage(rng, cap, obs_dim, act_dim):
    return TransitionBatch(
        obs=jnp.asarray(rng.standard_normal((cap, obs_dim)), jnp.float32),
        action=jnp.asarray(rng.uniform(-1, 1, (cap, act_dim)), jnp.float32),
        reward=jnp.asarray(rng.standard_normal(cap), jnp.float32),
        next_obs=jnp.asarray(rng.standard_normal((cap, obs_dim)), jnp.float32),
        done=jnp.zeros(cap, jnp.float32),
        discount=jnp.full(cap, 0.99, jnp.float32),
    )


def test_fused_chunk_per_step_and_priorities(rng):
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10, n_atoms=11,
                        hidden=(16, 16, 16))
    state = init_state(config, jax.random.key(0))
    storage = _fill_storage(rng, CAP, 4, 2)
    trees = dper.insert(dper.init(CAP), jnp.arange(CAP), 0.6)
    fn = make_fused_chunk(config, k=1, batch_size=8, alpha=0.6,
                          donate=False)
    state2, trees2, m = fn(state, trees, storage, CAP)
    assert int(state2.step) == int(state.step) + 1
    # with k=1 no resampling can overwrite: leaf at each sampled idx must
    # equal (|td| + eps) ** alpha (last write wins for duplicates)
    idx = np.asarray(m["idx"][0])
    td = np.asarray(m["td_error"][0])
    expect = (np.abs(td) + 1e-6) ** 0.6
    leaf = np.asarray(trees2.sum_tree[CAP + idx])
    for slot in np.unique(idx):
        cands = expect[idx == slot]
        assert np.any(np.isclose(leaf[idx == slot][0], cands, rtol=1e-4))


def test_fused_chunk_multi_step_advances_and_is_deterministic(rng):
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10, n_atoms=11,
                        hidden=(16, 16, 16))
    state = init_state(config, jax.random.key(0))
    storage = _fill_storage(rng, CAP, 4, 2)
    trees = dper.insert(dper.init(CAP), jnp.arange(CAP), 0.6)
    fn = make_fused_chunk(config, k=5, batch_size=8, donate=False)
    s1, t1, m1 = fn(state, trees, storage, CAP)
    s2, t2, m2 = fn(state, trees, storage, CAP)
    assert int(s1.step) == 5
    assert m1["critic_loss"].shape == (5,)
    np.testing.assert_array_equal(np.asarray(m1["idx"]), np.asarray(m2["idx"]))
    np.testing.assert_array_equal(np.asarray(t1.sum_tree),
                                  np.asarray(t2.sum_tree))
    assert np.isfinite(float(m1["critic_loss"][-1]))


def test_fused_chunk_mog_critic(rng):
    """The fused chunk composes with the mixture-of-Gaussians critic (the
    reference's empty stub, implemented for real): MoG TD errors feed the
    in-scan priority write-back like the categorical path."""
    config = D4PGConfig(obs_dim=4, act_dim=2, critic_family="mog",
                        n_components=3, hidden=(16, 16), mog_samples=8)
    state = init_state(config, jax.random.key(0))
    storage = _fill_storage(rng, CAP, 4, 2)
    trees = dper.insert(dper.init(CAP), jnp.arange(CAP), 0.6)
    fn = make_fused_chunk(config, k=2, batch_size=8, donate=False)
    s1, t1, m = fn(state, trees, storage, CAP)
    assert int(s1.step) == 2
    assert np.isfinite(np.asarray(m["critic_loss"])).all()
    assert not np.allclose(np.asarray(t1.sum_tree), np.asarray(trees.sum_tree))


def test_fused_chunk_uniform_variant(rng):
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10, n_atoms=11,
                        hidden=(16, 16, 16))
    state = init_state(config, jax.random.key(0))
    storage = _fill_storage(rng, CAP, 4, 2)
    fn = make_fused_chunk(config, k=3, batch_size=8, donate=False)
    state2, no_trees, m = fn(state, None, storage, jnp.int32(CAP))
    assert no_trees is None
    assert int(state2.step) == 3
    idx = np.asarray(m["idx"])
    assert idx.min() >= 0 and idx.max() < CAP


def test_fused_buffer_drain_overflow_keeps_newest(rng):
    """Staging more rows than the ring holds must keep exactly the newest
    ``capacity`` (the block drain lands rows sequentially; older slots
    are overwritten in order, never scatter-raced)."""
    buf = FusedDeviceReplay(CAP, 1, 1, prioritized=False)
    rows = np.arange(100, dtype=np.float32)[:, None]
    for lo in (0, 40):
        n = 60 if lo == 40 else 40
        r = rows[lo:lo + n]
        buf.add(TransitionBatch(
            obs=r, action=np.zeros((n, 1), np.float32),
            reward=r[:, 0], next_obs=r,
            done=np.zeros(n, np.float32),
            discount=np.ones(n, np.float32)))
    assert buf.drain() == 100  # all staged rows land (block-sequential)
    assert buf.size == CAP and buf.head == 100 % CAP
    got = np.sort(np.asarray(buf.storage.reward[:CAP]))
    np.testing.assert_array_equal(got, np.arange(100 - CAP, 100))


def test_fused_buffer_staging_is_bounded(rng):
    """Ingest while the learner is paused must not grow without bound:
    the preallocated staging ring drops the OLDEST rows under backlog
    (the next drains would overwrite them anyway), and drain still lands
    the newest rows."""
    buf = FusedDeviceReplay(CAP, 1, 1, prioritized=False)
    for i in range(20):  # 20 batches x 10 rows >> capacity 64
        r = np.full((10, 1), float(i), np.float32)
        buf.add(TransitionBatch(
            obs=r, action=np.zeros((10, 1), np.float32), reward=r[:, 0],
            next_obs=r, done=np.zeros(10, np.float32),
            discount=np.ones(10, np.float32)))
    assert len(buf._staging) <= buf._staging.size  # preallocated bound
    assert buf._staging.size <= 2 * CAP  # stays O(capacity)
    buf.drain()
    assert buf.size == CAP
    # the newest batches survived
    assert float(np.asarray(buf.storage.reward[:CAP]).max()) == 19.0


def test_train_fused_uniform_async(tmp_path):
    """End-to-end train() through the fused path with uniform replay and
    async actors (decoupled loop + remainder chunks: 18 = 8 + 8 + 2)."""
    from d4pg_tpu.config import ExperimentConfig
    from d4pg_tpu.train import train

    cfg = ExperimentConfig(
        env="point", max_steps=20, num_envs=2, warmup=100, n_epochs=1,
        n_cycles=2, episodes_per_cycle=1, train_steps_per_cycle=18,
        eval_trials=1, batch_size=16, memory_size=2000,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-5.0, v_max=0.0, replay_storage="device", fused_replay="on",
        prioritized_replay=False, async_actors=True,
    )
    metrics = train(cfg)
    assert np.isfinite(metrics["critic_loss"])


def test_train_fused_her_goal_env(tmp_path, capsys):
    """HER relabels stream through the fused device buffer like ordinary
    rows (goal-conditioned obs, success-based dones). ``train``'s
    ``plan:`` line says how the trees are repaired at the chunk's batch
    and at the commit's block, and that the min tree's side of the first
    step comes from the sum tree's leaves."""
    from d4pg_tpu.config import ExperimentConfig
    from d4pg_tpu.train import train

    cfg = ExperimentConfig(
        env="fake-goal", her=True, max_steps=10, warmup=80, n_epochs=1,
        n_cycles=2, episodes_per_cycle=2, train_steps_per_cycle=8,
        eval_trials=1, batch_size=16, memory_size=2000,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-5.0, v_max=0.0, replay_storage="device", fused_replay="on",
    )
    metrics = train(cfg)
    assert np.isfinite(metrics["critic_loss"])
    assert "success_rate" in metrics
    want = ("chunk:11>4 whole(min from sum),4>root whole;"
            "commit:11>4 whole(min from sum),4>root whole")
    assert metrics["plan"]["tree_repair"] == want
    plan_line, = [ln for ln in capsys.readouterr().out.splitlines()
                  if ln.startswith("plan: ")]
    assert f" tree_repair={want}" in plan_line


def test_fused_buffer_stage_drain(rng):
    buf = FusedDeviceReplay(CAP, 4, 2, alpha=0.6)
    batch = TransitionBatch(
        obs=rng.standard_normal((10, 4)).astype(np.float32),
        action=rng.uniform(-1, 1, (10, 2)).astype(np.float32),
        reward=rng.standard_normal(10).astype(np.float32),
        next_obs=rng.standard_normal((10, 4)).astype(np.float32),
        done=np.zeros(10, np.float32),
        discount=np.full(10, 0.99, np.float32),
    )
    buf.add(batch)
    assert len(buf) == 10 and buf.size == 0  # staged counts toward warmup
    n = buf.drain()
    assert n == 10 and buf.size == 10 and len(buf) == 10
    # tree mass: 10 live slots at max_priority**alpha == 1 (pad writes are
    # duplicates of slot 0, not extra mass)
    assert np.isclose(float(buf.trees.sum_tree[1]), 10.0)
    got = np.asarray(buf.storage.obs[:10])
    np.testing.assert_allclose(got, batch.obs, rtol=1e-6)
    # ring wrap: 60 more rows wrap over capacity 64
    big = TransitionBatch(*[np.repeat(np.asarray(v), 6, axis=0)
                            for v in batch])
    buf.add(big)
    buf.drain()
    assert buf.size == CAP and buf.head == (10 + 60) % CAP
