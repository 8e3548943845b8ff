"""Device-resident PER sum/min trees: priority state lives in HBM.

TPU-native redesign of the prioritized-replay data path (the reference
keeps its segment trees in host Python lists and walks them one sample at
a time, ``prioritized_replay_memory.py:33-162``). Every host round trip
is a blocking sync that costs more than a step of these small networks,
so the trees move onto the device next to the transition
ring (``replay/device_ring.py``) and the ENTIRE per-step replay protocol
— stratified proportional sampling, importance weights, priority
write-back — becomes pure ``jnp`` ops that fuse into the scanned learner
update (``learner/fused.py``). One dispatch then carries K grad steps
with zero host involvement and zero priority staleness (the reference
writes priorities once per step, ``ddpg.py:252-255``; the host-pipelined
chunk path bounds staleness by (depth+1)K; this path restores exact per-step
semantics *inside* the scan).

Layout matches the host trees (``replay/segment_tree.py``): the sum tree
is one flat array of ``2 * capacity`` (power of two) nodes, root at 1,
leaf ``i`` at ``capacity + i``. Unlike the host trees only the levels a
reader reads are maintained (:func:`kept_levels`): the leaves, every
seventh level above them, and the root. The min tree has NO LEAVES: its
only reader reads its root, so it holds the sum tree's kept levels above
the leaves (:func:`min_kept_levels`) under the sum tree's node numbers,
and the array is cut below the lowest of them (:func:`min_tree_nodes`:
``2 * capacity / 128`` nodes, 128 KB where the sum tree is 16 MB; two
nodes for a tree of 128 leaves or fewer, which keeps the root alone).
Its lowest kept level is taken from the SUM tree's leaves, so the
leaves are scattered once and both trees agree on the winner among
duplicates by construction.

THE INVARIANT. A kept node is the float32 sum (sum tree) or min (min
tree) of the 128 kept nodes seven levels under it (under the root, of
the ``2 ** first`` nodes of the first kept level), *taken as seven (or
``first``) rounds of adjacent pairs*: the value a tree that stored every
level as the ``+`` / ``min`` of its two children would hold there, to
the bit; under the min tree's lowest kept level the nodes are
``where(leaf > 0, leaf, inf)`` of the sum tree's leaves. A node of no
kept level (and node 0) is NEVER written and keeps what :func:`init`
gave it (0 / inf), so two buffers that reached the same leaves by
different batch shapes hold equal arrays.

THE EMPTY SLOT. A sum-tree leaf of exactly 0 is an empty slot for both
trees: it adds no mass, cannot be sampled and is left out of the minimum
(what :func:`init` means by 0 / inf, made a rule). No caller writes 0:
``update_from_td`` writes ``(|td| + eps) ** alpha``, ``insert`` and the
commit programs ``max_priority ** alpha >= 1``. A written 0 (or a
negative or NaN leaf) is ignored by the minimum, where a min tree with
leaves of its own would have turned every importance weight 0 or NaN.

All ops are batched:

  - ``set_leaves``: scatter the B leaves (of the sum tree: the one leaf
    scatter), then repair the kept levels above them, each from the kept
    level below it, in one of two forms chosen per step from the static
    capacity and B (:func:`repair_plan`): by the touched rows (gather
    the B rows of 128 nodes under the touched parents, total each by
    rounds of pairs, write B values; duplicates among the B write
    identical values and need no dedup), or whole (the level above
    recomputed from all of the one below, which is what a batch that
    touches most rows must get);
  - ``sample``: B stratified inverse-CDF queries descend in lock-step,
    seven levels at a gather: the 128 nodes seven levels below node ``n``
    are row ``n`` of the tree read as ``[2N / 128, 128]``, and the six
    levels between are that row's pairwise sums again (``descend``:
    log2(N) / 7 row gathers and log2(N) compare/where rounds, the slots
    of a walk that gathers B scalars a level);
  - trees are float32 (device-friendly); with ~1e6 leaves the prefix-sum
    rounding error is ~1e-7 of total mass per level — sampling noise well
    below the stochasticity already present. IS weights read exact leaf
    values.

Duplicate sampled indices within a batch: ``set_leaves`` keeps one
write-back winner per slot (scatter set), matching the reference's
last-write-wins sequential loop up to ordering; XLA leaves the winner
unspecified, and the min tree reads whichever the sum tree kept.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import Array

from d4pg_tpu.replay.segment_tree import next_pow2


class PerTrees(NamedTuple):
    """Device PER state; a pure pytree (donate/checkpoint-able)."""

    sum_tree: Array  # [2 * capacity] float32, node 1 is the root
    min_tree: Array  # [min_tree_nodes(capacity)] float32, no leaves
    max_priority: Array  # [] float32, running max of RAW priorities

    @property
    def capacity(self) -> int:
        return self.sum_tree.shape[0] // 2


def _levels(capacity: int) -> int:
    # capacity comes from Array.shape — a static Python int at trace time,
    # so this is host shape math (it sizes the descent loop), not a sync
    return int(math.log2(capacity))  # jaxlint: disable=host-sync-in-jit


def init(capacity: int) -> PerTrees:
    """Fresh trees for ``capacity`` (rounded up to a power of two) slots."""
    cap = next_pow2(int(capacity))
    return PerTrees(
        sum_tree=jnp.zeros(2 * cap, jnp.float32),
        min_tree=jnp.full(min_tree_nodes(cap), jnp.inf, jnp.float32),
        max_priority=jnp.ones((), jnp.float32),
    )


_LANES = 128  # a row of the tree read as [2 * capacity / 128, 128]
_ROW_LEVELS = _LANES.bit_length() - 1  # 7: binary levels a row spans

# Which form a step of the repair takes, from what each costs on the v5e
# (PERF.md, PR 37). By rows a step costs its B indices: a gather of B rows
# of 128 nodes, their totals and a scatter of B scalars into the level's
# own slice, ~40 ns an index for both trees and ~10 us at least. Whole it
# costs the bytes of the level below: a window a round over it, ~0.024 ns
# a node for both trees (50 us over 2^21 leaves) and ~1 us a window at
# least. A level more than ``_WHOLE_NODES_PER_LEAF * B`` nodes wide is
# therefore repaired by its touched rows, a narrower one whole.
_WHOLE_NODES_PER_LEAF = 4096


def kept_levels(capacity: int) -> tuple[int, ...]:
    """The levels of a ``capacity``-leaf tree (root 0, leaves
    ``log2(capacity)``) whose nodes are maintained, from the root down:
    the root, then every seventh level counted from the leaves. They are
    what :func:`descend` reads and what :func:`set_leaves` writes; the
    one definition keeps the reader and the writer from drifting."""
    levels = _levels(capacity)
    first = levels % _ROW_LEVELS or min(levels, _ROW_LEVELS)
    return (0,) * (first > 0) + tuple(range(first, levels + 1, _ROW_LEVELS))


def min_kept_levels(capacity: int) -> tuple[int, ...]:
    """The levels the MIN tree keeps: the sum tree's without the leaves
    (only its root is ever read, and its lowest kept level is made from
    the sum tree's leaves); the root alone for 128 leaves or fewer."""
    return kept_levels(capacity)[:-1] or (0,)


def min_tree_nodes(capacity: int) -> int:
    """The length of ``PerTrees.min_tree``: heap order under the sum
    tree's node numbers, cut below its lowest kept level."""
    return 2 << min_kept_levels(capacity)[-1]


def repair_plan(capacity: int, batch: int) -> tuple[tuple[int, int, str], ...]:
    """The steps of :func:`set_leaves` for ``batch`` leaves, from the
    leaves up: ``(kept level already right, kept level repaired from it,
    "rows" | "whole")``. Static shape arithmetic, so one compiled program
    a (capacity, B). The step under the root is one static slice of at
    most 128 nodes and always whole; once a step is whole so is every
    step above it (the levels only narrow)."""
    kept = kept_levels(capacity)
    return tuple(
        (below, above,
         "rows" if above and _WHOLE_NODES_PER_LEAF * batch < 1 << below
         else "whole")
        for above, below in zip(kept[-2::-1], kept[::-1]))


def plan_text(capacity: int, batch: int) -> str:
    """:func:`repair_plan` as ``train``'s ``plan:`` line prints it:
    ``21>14 rows(min from sum),14>7 whole,7>root whole``. The first step
    says where the min tree's side of it comes from: the sum tree's
    leaves, the one leaf level there is."""
    steps = [f"{below}>{above or 'root'} {form}"
             for below, above, form in repair_plan(capacity, batch)]
    if steps:
        steps[0] += "(min from sum)"
    return ",".join(steps)


def _parents(s: Array, m: Array) -> tuple[Array, Array]:
    """One level up for both trees, ``[rows, w] -> [rows, w / 2]``: the
    float32 ``+`` (sum tree) and ``min`` (min tree) of each adjacent pair,
    one operation a parent (the identities a window starts from change no
    bit of a priority). A (1, 2) window along the lanes is the one form of
    this the TPU runs near memory speed over a whole level;
    ``reshape(-1, 2).sum(-1)`` may be merged with the next level's into
    one reduction of four, which rounds differently, and costs 40 times
    as much; ``x[0::2] + x[1::2]`` 300 times (v5e; PERF.md, PR 29). Both
    trees share one window; over the leaves ``m`` is the sum tree's own
    level with its empty slots at ``inf`` (:func:`_min_side`), an
    elementwise producer of the window and no array of its own."""
    return jax.lax.reduce_window(
        (s, m), (jnp.float32(0), jnp.float32(jnp.inf)),
        lambda a, b: (a[0] + b[0], jnp.minimum(a[1], b[1])),
        (1, 2), (1, 2), "VALID")


def _min_side(leaves: Array) -> Array:
    """What the min tree reads of the sum tree's leaves: an empty slot
    (0; and what no priority can be, negative or NaN) is ``inf``."""
    return jnp.where(leaves > 0, leaves, jnp.float32(jnp.inf))


def _pair_rounds(row: Array, op=jnp.add):
    """The levels above a row of ``w`` nodes ([rows, w]), rebuilt in place
    on its lanes by log2(w) rounds of adjacent pairs, bottom up: yields
    ``(left, is_right, under)`` a round, where lane ``j`` holds for its
    ancestor at that level the ``op`` under the ancestor's LEFT child,
    whether ``j`` lies under the right one, and the ``op`` under the
    ancestor itself: ``op(left child, right child)``, one float32
    operation on two children, never a longer reduction (which XLA may
    merge with the next level's and round differently; PR 29). After the
    last round every lane of ``under`` holds the row's total: the node
    log2(w) levels above the row, as the invariant defines it."""
    width = row.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    under, span = row, 1
    while span < width:
        is_right = (lane & span) != 0  # lane j's ancestor at this level
        left = jnp.where(is_right, jnp.roll(under, span, axis=-1), under)
        right = jnp.where(is_right, under, jnp.roll(under, -span, axis=-1))
        under, span = op(left, right), 2 * span
        yield left, is_right, under


def _row_total(row: Array, op=jnp.add) -> Array:
    """``[rows, w] -> [rows]``: each row's total by rounds of adjacent
    pairs in place on its lanes (:func:`_pair_rounds`), one fusion over a
    gathered ``[B, 128]``."""
    for _left, _is_right, under in _pair_rounds(row, op):
        pass
    return under[:, 0]


def _level_totals(s: Array, m: Array) -> tuple[Array, Array]:
    """``[r, w] -> [r]`` for a whole level of both trees, ``w`` nodes a
    row: every row's total by log2(w) rounds of adjacent pairs, a window a
    round (:func:`_parents`), re-tiled to rows of ``w`` lanes while there
    is more than one; none of the levels in between is written. Measured
    against the rounds in place on the lanes (:func:`_row_total`, what a
    gathered ``[B, 128]`` takes): 176 us against 381 over the 2^21 leaves
    of both trees, and 4 us less over levels 14 and 7 (v5e; PERF.md,
    PR 37)."""
    width = s.shape[1]
    for _ in range(width.bit_length() - 1):
        s, m = _parents(s, m)
        if s.shape[0] > 1:
            s, m = s.reshape(-1, width), m.reshape(-1, width)
    return s.reshape(-1), m.reshape(-1)


def set_leaves(trees: PerTrees, idx: Array, p_alpha: Array) -> PerTrees:
    """Write ``p_alpha`` ([B], already ``priority ** alpha``) at leaves
    ``idx`` ([B] int) of the sum tree and repair the kept levels of both
    trees above them (:func:`kept_levels`, :func:`min_kept_levels`), each
    from the kept level below it, by the steps of :func:`repair_plan`:

    - **rows**: the B rows of 128 nodes under the touched parents are
      gathered (after the write below, so duplicates read identical
      rows), each totalled by rounds of adjacent pairs, and the B totals
      scattered into the level above; duplicates write identical values
      and need no dedup;
    - **whole**: the level above is recomputed from all of the level
      below, a window a round, and written as one static slice.

    The first step reads the SUM tree's leaves for both trees (by rows:
    one gather of the B rows; whole: one stream of windows over the
    leaves), totalled with ``+`` for the sum tree and with ``min`` over
    :func:`_min_side` of them for the min tree, which has no leaves of
    its own: one leaf scatter a call, and both trees see one winner
    among duplicates. Every step above reads each tree's own level.

    Either way a kept node ends as the invariant defines it (module
    docstring), to the bit; a node of no kept level is never written.

    PRECONDITION: the kept levels are consistent on entry. A whole step
    recomputes a level from all the nodes below, touched or not, so an
    inconsistent kept node elsewhere would be silently rewritten.
    ``init``, ``FusedDeviceReplay.load_state_dict`` / ``restore``
    (``init`` then this function) and ``ShardedFusedReplay
    .load_state_dict`` (the same rounds on the host) all hand over
    consistent trees.

    Entries with ``idx >= capacity`` are PADS and are dropped entirely:
    their node is parked out of bounds through every step by rows
    (``mode='drop'`` discards the writes; the paired gathers clamp but
    only feed dropped writes), and a whole step reads only the tree.
    Callers bucket batch sizes with such pads for compile-count control;
    a pad-only call changes nothing."""
    cap = trees.capacity
    idx32 = idx.astype(jnp.int32)
    valid = idx32 < cap
    # pads park at 2*cap (one past the array): writes there are dropped;
    # re-parked after every shift so they never alias a real node. (A
    # shifted-high sentinel like (2*cap) << levels would overflow int32
    # at realistic capacities: 2*cap^2 >= 2^41 for a 1M ring.)
    node = jnp.where(valid, idx32 + cap, 2 * cap)
    with jax.named_scope("writeback.leaves"):
        s = trees.sum_tree.at[node].set(p_alpha.astype(jnp.float32),
                                        mode="drop")
    m = trees.min_tree
    plan = repair_plan(cap, idx32.size)
    if not plan:  # one leaf: it is the root
        m = m.at[1].set(_min_side(s[1]))
    # every step makes the whole kept level above as an array of its own,
    # writes it as one static slice and hands it to the step above: a
    # whole step then reads what the step below made, not the tree again
    level = None
    for below, above, form in plan:
        width = 1 << above
        leaves = level is None  # the first step: both trees read `s`'s
        if form == "whole":
            with jax.named_scope("writeback.whole"):
                if leaves:
                    level = s[cap:], _min_side(s[cap:])
                lanes = min(1 << below, _LANES)  # under the root: one row
                level = _level_totals(*(x.reshape(-1, lanes)
                                        for x in level))
        else:
            with jax.named_scope("writeback.rows"):
                # the node _ROW_LEVELS up is the index of the row under it
                node = jnp.where(valid, node >> _ROW_LEVELS, 2 * cap)
                row_s = s.reshape(-1, _LANES)[
                    jnp.minimum(node, 2 * cap // _LANES - 1)]
                row_m = _min_side(row_s) if leaves else m.reshape(
                    -1, _LANES)[jnp.minimum(node, m.size // _LANES - 1)]
                totals = _row_total(row_s), _row_total(row_m, jnp.minimum)
                # B scalars cost ~86 ns an index scattered into the 16 MB
                # tree where it lies in HBM and ~6 into the level's own
                # slice, which the compiler keeps in fast memory (24.6
                # against 1.6 us a tree at level 14; v5e, PERF.md, PR 37)
                at = jnp.where(valid, node - width, width)
                level = tuple(
                    t[width:2 * width].at[at].set(x, mode="drop")
                    for t, x in zip((s, m), totals))
        s, m = (jax.lax.dynamic_update_slice(t, x, (width,))
                for t, x in zip((s, m), level))
    return PerTrees(s, m, trees.max_priority)


def insert(trees: PerTrees, idx: Array, alpha: float) -> PerTrees:
    """New transitions enter with ``max_priority ** alpha``
    (``prioritized_replay_memory.py:251-256``). Pad ``idx`` with
    ``capacity`` (dropped) to bucket sizes for compile-count control."""
    p = jnp.full(idx.shape, trees.max_priority**alpha, jnp.float32)
    return set_leaves(trees, idx, p)


def update_from_td(
    trees: PerTrees, idx: Array, td_error: Array, alpha: float,
    eps: float = 1e-6,
) -> PerTrees:
    """Priority write-back from the TD errors of a sampled batch
    (``ddpg.py:252-255``: priority = |td| + eps, stored as ``p ** alpha``,
    running max tracked on the raw priority)."""
    p = jnp.abs(td_error) + eps
    trees = set_leaves(trees, idx, p**alpha)
    return trees._replace(
        max_priority=jnp.maximum(trees.max_priority, p.max())
    )


def strata_mass(u: Array, total: Array) -> Array:
    """Stratified prefix masses from unit uniforms ``u`` ([..., B]):
    stratum ``i`` draws mass ``(i + u_i) * (total / B)``. Factored out so
    the host twin oracle (``sampler.SampleDealer`` in ``dtype='float32'``
    mode) can reproduce the exact float32 arithmetic with numpy — add,
    divide and multiply are correctly-rounded IEEE ops, bitwise identical
    between numpy and XLA CPU (unlike ``**``, see :func:`block_weights`)."""
    b = u.shape[-1]
    return (jnp.arange(b) + u) * (total / b)


def _walk_row(row: Array, p: Array) -> tuple[Array, Array]:
    """The binary decisions below a node whose ``w`` descendants log2(w)
    levels down hold ``row`` ([B, w], or [1, w] where every query stands
    at one node), for prefix masses ``p`` ([B]): ``(p, pos)``, ``pos``
    the descendant each query reaches and ``p`` what is left of its mass.

    Nothing is looked up. Every lane ``j`` of the row walks the path to
    descendant ``j``, all of them at once:

    - the levels between node and row are rebuilt from the row, in place
      on its lanes: ``left`` holds, in lane ``j``, the sum under the LEFT
      child of ``j``'s ancestor at that level, and ``left + right`` the
      sum under the ancestor (:func:`_pair_rounds`: the rounds of pairs
      the invariant is stated in, so the rebuilt node is the value a
      stored level would hold, to the bit);
    - from the top down each lane makes the walk's own compare and
      subtract against its ancestor's left child. Lanes under one node
      hold one mass, so they decide alike, and the half whose own bit is
      the other way leave the path: one lane is left on it, the one the
      level-by-level walk reaches, with that walk's mass to the bit. The
      two sums that read it off are exact (every other term is 0).

    Of the forms measured on the v5e (PERF.md, PR 35) this is the one the
    chunk runs fastest: elementwise work on [B, 128] that fuses, against
    a compare-select-sum a level for a looked-up ``left`` (as fast alone,
    0.7 ms a 40-step chunk slower: with it the compiler moves both trees
    into ``S(1)`` behind the update, where they wait for each other) and
    ``take_along_axis``, a lane gather (3 times the walk's time)."""
    width = row.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    steps = [(left, is_right)
             for left, is_right, _under in _pair_rounds(row)][::-1]
    mass = jnp.broadcast_to(p[:, None], (p.shape[0], width))
    on_path = jnp.ones(mass.shape, bool)
    for left, is_right in steps:  # from the top down
        go_right = mass >= left
        on_path &= go_right == is_right
        mass = jnp.where(go_right, mass - left, mass)
    pos = jnp.sum(jnp.where(on_path, lane, 0), axis=-1)
    return jnp.sum(jnp.where(on_path, mass, jnp.float32(0)), axis=-1), pos


def descend(sum_tree: Array, mass: Array) -> Array:
    """Lock-step inverse-CDF descent of prefix masses ``mass`` (any
    shape) through ``sum_tree`` ([2 * capacity]); returns leaf slots.

    TIE RULE (the bitwise-oracle contract, shared with the host trees'
    ``segment_tree.SumTree.find_prefixsum`` / ``ShardSlicePerTrees``): at
    every node, ``mass >= left_subtree_sum`` descends RIGHT (and
    subtracts); strictly less descends left. A prefix equal to a left
    subtree's sum therefore always resolves to the first leaf of the
    RIGHT subtree — in particular a zero-mass query at a zero-priority
    left leaf skips to the first nonzero leaf, and duplicate prefix
    values (two strata colliding after float rounding) resolve to the
    same slot on host and device alike.

    HOW THE TREE IS READ. Not a node a level (log2(capacity) dependent
    gathers of B scalars, ~7 us each at 2^21 leaves on the v5e) but a row
    of ``_LANES`` nodes every ``_ROW_LEVELS`` levels: in the flat heap the
    128 descendants seven levels below node ``n`` are nodes ``128 n ..
    128 n + 127``, row ``n`` of ``sum_tree.reshape(-1, 128)`` (the same
    bytes: no copy), and the invariant lets :func:`_walk_row` rebuild the
    six levels between from the row: the rows read are the kept levels
    (:func:`kept_levels`), the only ones ``set_leaves`` maintains. The
    levels left over when log2(capacity) is no multiple of seven come
    first, from ONE static slice under the root (a tree of under 128
    leaves is that slice and nothing else); the step count follows the
    static capacity, so there is one compiled walk a (capacity, B). Every
    decision is the float32 compare and subtract the level-by-level walk
    makes, on the same values in the same order, so the slots are that
    walk's to the bit (tests/test_device_per.py keeps it as the oracle)."""
    cap = sum_tree.shape[0] // 2
    kept = kept_levels(cap)
    p = mass.reshape(-1)
    node = jnp.ones(p.shape, jnp.int32)
    for above, below in zip(kept, kept[1:]):
        if above == 0:  # every query stands at the root
            p, pos = _walk_row(sum_tree[1 << below:2 << below][None], p)
            node = (1 << below) + pos
        else:
            p, pos = _walk_row(sum_tree.reshape(-1, _LANES)[node], p)
            node = node * _LANES + pos
    return (node - cap).reshape(mass.shape)


def sample_from_uniforms(trees: PerTrees, u: Array, limit: Array) -> Array:
    """Stratified proportional sampling from caller-supplied unit
    uniforms ``u`` ([..., B]) — the descent half of :func:`sample`, split
    out so the dealt plane can feed uniforms drawn from the dealer's
    seeded HOST stream (the bitwise-oracle stream) instead of a device
    PRNG key. ``limit`` clips prefix overshoot onto written leaves."""
    total = trees.sum_tree[1]
    idx = descend(trees.sum_tree, strata_mass(u, total))
    return jnp.minimum(idx, jnp.maximum(limit - 1, 0))


def sample(
    trees: PerTrees, key: Array, batch_size: int, limit: Array
) -> Array:
    """Stratified proportional sampling: B strata over the total mass, one
    uniform draw each, lock-step inverse-CDF descent (the vectorized form
    of ``prioritized_replay_memory.py:258-265``). ``limit`` (traced int,
    the buffer's live size) clips prefix overshoot onto written leaves."""
    u = jax.random.uniform(key, (batch_size,))
    return sample_from_uniforms(trees, u, limit)


def is_weights(
    trees: PerTrees, idx: Array, beta: Array, size: Array
) -> Array:
    """``(p_i * N) ** -beta`` normalized by the max weight (computed from
    the min tree) — ``prioritized_replay_memory.py:299-313``."""
    total = trees.sum_tree[1]
    n = size.astype(jnp.float32)
    p_min = trees.min_tree[1] / total
    max_weight = (p_min * n) ** (-beta)
    p = trees.sum_tree[trees.capacity + idx] / total
    return ((p * n) ** (-beta) / max_weight).astype(jnp.float32)


def block_weights(
    total: Array, min_root: Array, leaf_p: Array, beta: Array, size: Array
) -> Array:
    """IS weights for a dealt block from its tree scalars and gathered
    leaf priorities — the float32 mirror of the host dealer's
    ``_draw_block_locked`` weight expression (``weight_base`` +
    ``(p * N) ** -beta / max_weight``).

    Kept as ONE shared function because float32 ``**`` is NOT bitwise
    portable between numpy and XLA (measured 1-ulp divergence on CPU):
    the device deal dispatch and the host twin oracle both call the SAME
    compiled transform (:func:`block_weights_jitted`), so the oracle's
    weight comparison is exact by construction instead of hostage to
    libm rounding."""
    n = size.astype(jnp.float32)
    z = min_root / total * n
    max_weight = z ** (-beta)
    p = leaf_p / total
    return ((p * n) ** (-beta) / max_weight).astype(jnp.float32)


_block_weights_jit = None


def block_weights_jitted(total, min_root, leaf_p, beta, size) -> Array:
    """Dispatch :func:`block_weights` as one cached jit — the single
    compiled artifact both the device dealer and the twin oracle share."""
    global _block_weights_jit
    if _block_weights_jit is None:
        _block_weights_jit = jax.jit(block_weights)
    return _block_weights_jit(total, min_root, leaf_p, beta, size)


_set_leaves_jit = None


def set_leaves_jitted(trees: PerTrees, idx, p_alpha) -> PerTrees:
    """Dispatch :func:`set_leaves` as ONE device computation (eager jnp
    pays one dispatch per op — two or three a level of tree repair;
    checkpoint restore rebuilds the whole tree this way).
    Donates ``trees``; caller owns the handle."""
    global _set_leaves_jit
    if _set_leaves_jit is None:
        _set_leaves_jit = jax.jit(set_leaves, donate_argnums=(0,))
    return _set_leaves_jit(trees, idx, p_alpha)


_insert_jit = None


def insert_jitted(trees: PerTrees, idx, alpha: float) -> PerTrees:
    """Dispatch :func:`insert` as ONE device computation (eager jnp would
    pay one dispatch per op). Donates ``trees``
    — the caller must own the handle (single-writer: the learner thread).
    Callers bucket ``idx`` length (pad by repeating a live slot) so only
    O(log n) shapes compile."""
    global _insert_jit
    if _insert_jit is None:
        _insert_jit = jax.jit(insert, static_argnames=("alpha",),
                              donate_argnums=(0,))
    return _insert_jit(trees, idx, alpha=alpha)


def beta_schedule(step: Array, beta0: float, beta_steps: int) -> Array:
    """PER beta annealing as a pure in-jit function of the learner step —
    the device twin of ``replay/schedule.py``'s LinearSchedule (beta0 -> 1
    over ``beta_steps``, then clamped)."""
    frac = jnp.clip(step.astype(jnp.float32) / float(beta_steps), 0.0, 1.0)
    return beta0 + frac * (1.0 - beta0)
