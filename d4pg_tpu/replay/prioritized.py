"""Prioritized experience replay with vectorized proportional sampling.

Parity: the reference's ``PrioritizedReplayBuffer``
(``prioritized_replay_memory.py:224-335``):

  - new transitions enter with priority ``max_priority ** alpha`` (``:251-256``),
  - proportional sampling by inverse-CDF over the sum tree (``:258-265``),
  - importance-sampling weights ``(p_i * N) ** -beta`` normalized by the max
    weight, computed from the min tree (``:299-313``),
  - ``update_priorities`` writes ``priority ** alpha`` into both trees and
    tracks the running max (``:315-335``).

Differences: all operations are batched numpy (or the C++ native sampler,
``backend='native'`` / ``native/per_trees.cpp``); sampling segments the
total mass into B strata (one uniform draw per stratum), which is the
standard variance-reduction refinement of the reference's B independent
uniform draws (``:263-264``) — set ``stratified=False`` for the
reference's exact scheme.
"""

from __future__ import annotations

import numpy as np

from d4pg_tpu.replay.segment_tree import MinTree, SumTree
from d4pg_tpu.replay.uniform import ReplayBuffer, TransitionBatch


class _NumpyPerTrees:
    """Sum+min tree pair behind the combined interface the buffer uses
    (the native backend implements the same one in C++)."""

    def __init__(self, capacity: int):
        self._sum_tree = SumTree(capacity)
        self._min_tree = MinTree(capacity)
        self.capacity = self._sum_tree.capacity

    def set(self, idx: np.ndarray, values: np.ndarray) -> None:
        self._sum_tree.set(idx, values)
        self._min_tree.set(idx, values)

    def sum(self) -> float:
        return self._sum_tree.sum()

    def min(self) -> float:
        return self._min_tree.min()

    def get(self, idx: np.ndarray) -> np.ndarray:
        return self._sum_tree.get(idx)

    def find_prefixsum(self, prefix: np.ndarray) -> np.ndarray:
        return self._sum_tree.find_prefixsum(prefix)


def _make_trees(capacity: int, backend: str):
    if backend not in ("auto", "numpy", "native"):
        raise ValueError(f"unknown PER backend {backend!r}")
    if backend in ("auto", "native"):
        try:
            from d4pg_tpu.replay.native import NativePerTrees

            return NativePerTrees(capacity)
        except (RuntimeError, OSError):
            if backend == "native":
                raise
    return _NumpyPerTrees(capacity)


class PrioritizedReplayBuffer(ReplayBuffer):
    def __init__(
        self,
        capacity: int,
        obs_dim: int,
        act_dim: int,
        alpha: float = 0.6,
        seed: int = 0,
        stratified: bool = True,
        backend: str = "auto",
        obs_dtype=None,
        storage: str = "host",
        device=None,
    ):
        super().__init__(capacity, obs_dim, act_dim, seed=seed,
                         obs_dtype=obs_dtype, storage=storage, device=device)
        assert alpha >= 0
        self.alpha = float(alpha)
        self.stratified = bool(stratified)
        self._trees = _make_trees(self.capacity, backend)
        self.max_priority = 1.0
        # Per-slot write generation: with async actors, a slot sampled by
        # the learner can be overwritten by the drain thread before the TD
        # error comes back; a generation captured at sample time lets
        # update_priorities drop those writes instead of stamping a stale
        # priority onto a brand-new transition.
        self.generation = np.zeros(self.capacity, np.int64)

    @property
    def tree_backend(self) -> str:
        """Which host tree loaded: ``'native'`` (the C++ extension) or
        ``'numpy'`` (its fallback when the library is absent)."""
        return ("numpy" if isinstance(self._trees, _NumpyPerTrees)
                else "native")

    def add(self, batch: TransitionBatch) -> np.ndarray:
        idx = super().add(batch)
        self.generation[idx] += 1
        p = self.max_priority**self.alpha
        self._trees.set(idx, np.full(len(idx), p))
        return idx

    def sample_idx(self, batch_size: int) -> np.ndarray:
        if self.size == 0:
            raise ValueError("cannot sample from an empty buffer")
        total = self._trees.sum()
        if self.stratified:
            bounds = np.linspace(0.0, total, batch_size + 1)
            mass = self._rng.uniform(bounds[:-1], bounds[1:])
        else:
            mass = self._rng.uniform(0.0, total, size=batch_size)
        idx = self._trees.find_prefixsum(mass)
        # guard: prefix just at/over the total can land on an unwritten leaf
        return np.minimum(idx, max(self.size - 1, 0))

    def weight_base(self) -> float:
        """``z = (p_min / total) * N`` — the scalar whose ``z ** -beta`` is
        the max IS weight. Multi-host sharded replay allgather-mins this
        across hosts so every shard normalizes by the same global max
        weight (per-host normalizers would scale gradient contributions
        inconsistently across hosts)."""
        total = self._trees.sum()
        return float(self._trees.min() / total * self.size)

    def is_weights(
        self, idx: np.ndarray, beta: float,
        weight_base: float | None = None,
    ) -> np.ndarray:
        """(p_i * N)^-beta / max_weight, max via the min tree
        (``prioritized_replay_memory.py:299-311``). ``weight_base``
        overrides the local ``z`` (see :meth:`weight_base`)."""
        assert beta > 0
        total = self._trees.sum()
        z = self.weight_base() if weight_base is None else weight_base
        max_weight = z ** (-beta)
        p = self._trees.get(idx) / total
        return ((p * self.size) ** (-beta) / max_weight).astype(np.float32)

    def sample(
        self, batch_size: int, beta: float = 0.4,
        weight_base: float | None = None,
    ) -> tuple[TransitionBatch, np.ndarray, np.ndarray]:
        """Returns (batch, is_weights, idx); idx feeds update_priorities."""
        idx = self.sample_idx(batch_size)
        return self.gather(idx), self.is_weights(idx, beta, weight_base), idx

    def sample_chunk(
        self, k: int, batch_size: int, beta: float = 0.4,
        weight_base: float | None = None,
    ) -> tuple[TransitionBatch, np.ndarray, np.ndarray]:
        """K stacked proportional samples in ONE storage gather: (batches
        [K, B, ...], weights [K, B], idx [K, B]). Tree walks and IS weights
        stay on the host; with device storage only the idx array crosses."""
        idx = np.stack([self.sample_idx(batch_size) for _ in range(k)])
        w = np.stack([self.is_weights(idx[i], beta, weight_base)
                      for i in range(k)])
        return self.gather(idx), w.astype(np.float32), idx

    def state_dict(self) -> dict:
        d = super().state_dict()
        # leaves already hold priority ** alpha; restore writes them back
        # verbatim (only live slots — unwritten min-tree leaves must stay
        # at the +inf neutral or p_min collapses to 0)
        d["leaf_priorities"] = np.asarray(
            self._trees.get(np.arange(self.size)))
        d["max_priority"] = self.max_priority
        d["generation"] = self.generation.copy()
        return d

    def load_state_dict(self, d: dict) -> None:
        super().load_state_dict(d)
        if self.size:
            self._trees.set(np.arange(self.size), d["leaf_priorities"])
        self.max_priority = float(d["max_priority"])
        self.generation = np.asarray(d["generation"]).copy()

    def update_priorities(
        self,
        idx: np.ndarray,
        priorities: np.ndarray,
        generation: np.ndarray | None = None,
    ) -> None:
        """Write ``priority ** alpha`` into the trees
        (``prioritized_replay_memory.py:315-335``). When ``generation``
        (captured at sample time) is given, entries whose slot has since
        been overwritten are dropped."""
        priorities = np.asarray(priorities, np.float64)
        assert (priorities > 0).all(), "priorities must be positive"
        if generation is not None:
            live = self.generation[idx] == generation
            if not live.all():
                idx, priorities = idx[live], priorities[live]
            if len(idx) == 0:
                return
        self._trees.set(idx, priorities**self.alpha)
        self.max_priority = max(self.max_priority, float(priorities.max()))
