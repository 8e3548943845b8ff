"""Partition rules: the single source of sharding truth.

Every ``PartitionSpec``/``NamedSharding`` in the framework is built HERE
(jaxlint family 15, ``sharding-rule-bypass``, rejects construction
anywhere else). Two layers:

**Layout helpers** — the fixed data-plane layouts the learner dispatches
use (batch over ``data``, [K, B] stacks with the scan axis replicated,
replica-stacked trees over ``replica``). Callers say what the array IS
(``batch_sharding(mesh)``) instead of hand-wiring axis tuples at every
jit site.

**Regex partition rules** — for *named parameter/optimizer trees* the
layout is decided by a rule table: ``(pattern, spec)`` pairs matched
against '/'-joined tree paths (the SAME names the weight codec's
flattened keys use — ``named_flat`` here is what the weight and update
planes serialize, so the wire naming and the sharding naming cannot
drift). Matching semantics, pinned by ``tests/test_partition.py``:

- scalar leaves (ndim 0 or size 1 — ``step``, Adam ``count``, PRNG key)
  are NEVER partitioned, before any rule is consulted;
- first match wins (``re.search``, table order = precedence);
- a leaf no rule matches fails LOUDLY with the resolved table in the
  message — silent replication is how layouts rot.

``D4PG_RULES`` is the production table: the pixel conv encoder is the
first ``model``-axis tenant (kernels/biases split over out-channels —
the SURVEY §2 mandate the axis was reserved for), everything else
replicated. The rules apply identically to params and to the Adam
moments that mirror them, because ``re.search`` finds the param path
inside the optimizer path (``actor_opt_state/0/mu/params/...``).
"""

from __future__ import annotations

import re
from typing import Any, Callable

import numpy as np

from d4pg_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS, REPLICA_AXIS

from jax.sharding import (Mesh, NamedSharding, PartitionSpec as PS,
                          SingleDeviceSharding)

__all__ = [
    "PS", "D4PG_RULES", "named_tree_map", "tree_names",
    "match_partition_rules", "format_rules", "spec", "sharding",
    "replicated", "one_device", "batch_sharding", "stacked_sharding",
    "replica_sharding",
    "batch_spec", "replicated_spec", "stacked_spec", "replica_spec",
    "per_tree_spec", "dealt_block_spec", "per_tree_sharding",
    "dealt_block_sharding",
    "data_spec", "shardings_for", "state_specs", "state_shardings",
    "replica_stack_shardings", "make_shard_and_gather_fns",
    "named_flat", "named_unflat", "expert_share",
]


# --------------------------------------------------------------------------
# expert parallelism: which experts a chip holds
# --------------------------------------------------------------------------
def expert_share(num_experts: int, n_shares: int, index: int) -> tuple:
    """``[lo, hi)`` of the experts that share ``index`` of ``n_shares``
    holds: a contiguous, equal range. An expert layer is told its range
    (``TorsoSpec.experts_held``), routes over all ``num_experts`` and adds
    its own experts' part of the result (``models/torso.expert_share``).
    On one chip the layer runs without its exchange; across chips the
    parts meet in an all-to-all that no code here stands in for."""
    if num_experts % n_shares or not 0 <= index < n_shares:
        raise ValueError(
            f"{num_experts} experts do not divide into {n_shares} equal "
            f"shares, or share {index} is not one of them")
    held = num_experts // n_shares
    return index * held, (index + 1) * held


# --------------------------------------------------------------------------
# fixed data-plane layouts
# --------------------------------------------------------------------------


def spec(*axes) -> PS:
    """A raw ``PartitionSpec`` — the one sanctioned constructor for
    layouts the helpers below don't name (e.g. per-call shard_map
    in_specs). Prefer the named helpers where one fits."""
    return PS(*axes)


def sharding(mesh: Mesh, *axes) -> NamedSharding:
    """``NamedSharding`` over ``mesh`` for an explicit axis layout."""
    return NamedSharding(mesh, PS(*axes))


def replicated_spec() -> PS:
    return PS()


def batch_spec() -> PS:
    """[B, ...] batches: leading dim split over ``data``."""
    return PS(DATA_AXIS)


# alias: shard_map call sites read better as "the data-axis spec"
data_spec = batch_spec


def stacked_spec() -> PS:
    """[K, B, ...] chunk stacks: K replicated (the scan axis), B split
    over ``data``."""
    return PS(None, DATA_AXIS)


def replica_spec() -> PS:
    """[N, ...] replica-stacked trees: leading dim split over
    ``replica`` (the mesh-native learner-replica layout)."""
    return PS(REPLICA_AXIS)


def per_tree_spec() -> PS:
    """Device PER trees (``replay/device_per.PerTrees``: the sum tree
    [2·cap], the min tree [2·cap/128], which has no leaves):
    REPLICATED. The stratified descent is a root-to-leaf pointer chase —
    every query touches every level, so splitting the tree over any mesh
    axis would turn each of the log2(cap) gathers into a collective.
    Keeping the tree replicated keeps the jitted deal dispatch at zero
    all-to-alls (the ReshardSentinel pin in tests/test_devsample.py) at
    a memory cost of 8 bytes/slot/device."""
    return PS()


def dealt_block_spec() -> PS:
    """[K, B, ...] device-dealt gathers (rows, weights, idx, gen out of
    ``DeviceSampleDealer.deal_fn``): same layout as the chunk stacks
    they feed — K replicated (the scan axis), B split over ``data``.
    With the tree replicated (``per_tree_spec``) the gather itself needs
    no resharding to land here."""
    return stacked_spec()


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, replicated_spec())


def one_device(device=None) -> SingleDeviceSharding:
    """The whole array on one device (the process's first when none is
    named): what the one-device replay ring, and the trees and learner
    state that travel with it, are committed to. A pinned layout is
    honoured only on a committed array (``replay/device_ring.py``)."""
    import jax

    return SingleDeviceSharding(
        device if device is not None else jax.local_devices()[0])


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def stacked_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, stacked_spec())


def replica_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, replica_spec())


def per_tree_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, per_tree_spec())


def dealt_block_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, dealt_block_spec())


# --------------------------------------------------------------------------
# named trees: one naming scheme for rules AND the wire codecs
# --------------------------------------------------------------------------


def named_tree_map(fn: Callable[[str, Any], Any], tree: Any,
                   sep: str = "/") -> Any:
    """Structure-preserving map with the leaf's '/'-joined path name.

    Handles the shapes that actually occur in a ``D4PGState``: dicts
    (flax param trees — key names), NamedTuples (the state itself, optax
    ``ScaleByAdamState``... — field names), plain lists/tuples (optax
    ``chain`` — indices). ``None`` leaves pass through (optax uses them
    as empty slots). Dict naming matches flax's ``flatten_dict(sep='/')``
    exactly — the weight codec's key grammar.
    """

    def join(prefix: str, part: str) -> str:
        return f"{prefix}{sep}{part}" if prefix else part

    def walk(prefix: str, node: Any) -> Any:
        if isinstance(node, PS):
            # PartitionSpec subclasses tuple on some jax versions —
            # always a leaf here (spec trees map through this fn too)
            return fn(prefix, node)
        if isinstance(node, dict):
            return {k: walk(join(prefix, str(k)), v) for k, v in node.items()}
        if isinstance(node, tuple) and hasattr(node, "_fields"):
            return type(node)(*[walk(join(prefix, f), getattr(node, f))
                                for f in node._fields])
        if isinstance(node, (list, tuple)):
            vals = [walk(join(prefix, str(i)), v) for i, v in enumerate(node)]
            return vals if isinstance(node, list) else tuple(vals)
        if node is None:
            return None
        return fn(prefix, node)

    return walk("", tree)


def tree_names(tree: Any, sep: str = "/") -> list[str]:
    """The '/'-joined leaf names of ``tree``, in traversal order."""
    names: list[str] = []
    named_tree_map(lambda name, leaf: names.append(name) or leaf, tree,
                   sep=sep)
    return names


def named_flat(params: Any) -> dict[str, np.ndarray]:
    """Flatten a nested dict pytree to ``{'a/b/c': array}`` — THE wire
    naming: the weight plane's codec keys and the update plane's
    submission payloads are exactly these names, and the rule table
    above matches against them. Uses flax's own param-dict flattening so
    key semantics match Flax exactly."""
    from flax.traverse_util import flatten_dict

    return {k: np.asarray(v)
            for k, v in flatten_dict(params, sep="/").items()}


def named_unflat(flat: dict[str, np.ndarray]) -> Any:
    """Invert :func:`named_flat`."""
    from flax.traverse_util import unflatten_dict

    return unflatten_dict(dict(flat), sep="/")


# --------------------------------------------------------------------------
# the rule engine
# --------------------------------------------------------------------------

# (pattern, spec): first match wins. The pixel conv encoder is the
# model-axis tenant — kernels [3, 3, in, out] and biases [out] split
# over out-channels (channel counts are MXU-friendly multiples of the
# model degree); everything else — MLP trunks, LayerNorm scales, Adam
# moments of all of the above — replicated.
D4PG_RULES: tuple[tuple[str, PS], ...] = (
    (r"encoder/conv\d+/kernel", PS(None, None, None, MODEL_AXIS)),
    (r"encoder/conv\d+/bias", PS(MODEL_AXIS)),
    (r".*", PS()),
)


def _is_scalar(leaf: Any) -> bool:
    shape = tuple(getattr(leaf, "shape", ()))
    return len(shape) == 0 or int(np.prod(shape)) == 1


def format_rules(rules=D4PG_RULES) -> str:
    """The resolved rule table, one ``pattern -> spec`` row per line —
    what ``check_mesh_compatible`` and the unmatched-key error print."""
    width = max(len(p) for p, _ in rules)
    return "\n".join(f"  {p:<{width}}  ->  {s}" for p, s in rules)


def match_partition_rules(rules, tree: Any) -> Any:
    """Resolve ``tree`` to a structure-matching tree of PartitionSpecs.

    Scalar leaves (ndim 0 or size 1) are never partitioned; otherwise
    the first ``re.search`` match in table order decides. A leaf nothing
    matches raises with the leaf's name and the table."""

    def resolve(name: str, leaf: Any) -> PS:
        if _is_scalar(leaf):
            return PS()
        for pattern, s in rules:
            if re.search(pattern, name):
                return s
        raise ValueError(
            f"no partition rule matches leaf {name!r}; resolved table:\n"
            f"{format_rules(rules)}")

    return named_tree_map(resolve, tree)


def shardings_for(mesh: Mesh, tree: Any, rules=D4PG_RULES) -> Any:
    """Rule-resolved ``NamedSharding`` tree for ``tree`` over ``mesh``."""
    return named_tree_map(
        lambda name, s: NamedSharding(mesh, s),
        match_partition_rules(rules, tree))


def state_specs(config, rules=D4PG_RULES) -> Any:
    """Rule-resolved PartitionSpec tree for a ``D4PGState`` of this
    config — structure derived via ``eval_shape`` (no arrays built)."""
    import jax

    from d4pg_tpu.learner.state import init_state

    shapes = jax.eval_shape(
        lambda: init_state(config, jax.random.key(0)))
    return match_partition_rules(rules, shapes)


def state_shardings(config, mesh: Mesh, rules=D4PG_RULES) -> Any:
    """Rule-resolved ``NamedSharding`` tree for a ``D4PGState`` — the
    in/out_shardings the sharded update factories pass to jit."""
    return named_tree_map(lambda name, s: NamedSharding(mesh, s),
                          state_specs(config, rules))


def replica_stack_shardings(mesh: Mesh, tree: Any,
                            rules=D4PG_RULES) -> Any:
    """Rule specs with the ``replica`` axis prepended: the layout of an
    [N, ...]-stacked tree of per-replica states on a replica mesh (the
    inner axes keep their rule-resolved placement; on the
    ``replica_mesh`` geometry those axes are singleton, so every rule
    stays satisfiable)."""
    return named_tree_map(
        lambda name, s: NamedSharding(mesh, PS(REPLICA_AXIS, *s)),
        match_partition_rules(rules, tree))


def make_shard_and_gather_fns(shardings: Any) -> tuple[Any, Any]:
    """Per-leaf shard/gather callables for a ``NamedSharding`` tree:
    ``shard_fns`` place host leaves (``device_put`` with the leaf's
    sharding), ``gather_fns`` pull them back to host numpy. Apply with
    ``jax.tree_util.tree_map(lambda f, x: f(x), fns, tree)``."""
    import jax

    def shard_fn(s):
        return lambda leaf: jax.device_put(leaf, s)

    def gather_fn(_s):
        return lambda leaf: np.asarray(jax.device_get(leaf))

    shard_fns = jax.tree_util.tree_map(
        shard_fn, shardings,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    gather_fns = jax.tree_util.tree_map(
        gather_fn, shardings,
        is_leaf=lambda x: isinstance(x, NamedSharding))
    return shard_fns, gather_fns
