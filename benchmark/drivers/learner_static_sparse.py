"""``learner_static_torso`` for a configuration whose torso selects the keys
its attention reads (``layer_types`` of ``sparse_attention``): the same
set-up, window and numbers, with the check against
``benchmark/reference_sparse.py``.

What differs from ``TorsoCell``, and why:

- **the reference** is ``reference_sparse.follow``.
- **select_hist_gap**: the chunk reports how many of the differentiated
  pass's selections fell on each block of ``kv_chunk_size`` keys
  (``select_counts [K, layers, tokens / kv_chunk_size]``); the first step's
  histograms are compared with the reference's, the largest difference over
  layers as a share of the layer's selections. The selection is exact on
  both sides; what moves a selection from one block to another is a score
  within rounding of a row's ``topk``-th.
- **index_loss_gap**: the worst per-step relative gap of the indexer's
  alignment loss (``index_loss [K]``).
- the last chunk's ``select_counts`` go to ``select_kept_share``, its
  ``route_counts`` to the expert readers, under the ``sparse`` key of the
  readers' context: ``benchmark/sparse_trace.py`` reads this cell, and
  ``benchmark/torso_trace.py`` (which reads ``torso``) finds nothing.
"""

from __future__ import annotations

import numpy as np

from benchmark import (
    cellbuild,
    datagen,
    reference,
    reference_sparse,
    shapes_sparse,
)
from benchmark.drivers.learner_static_torso import (
    TorsoCell,
    seeded_params,
)
from benchmark.learner import RunEnv, report

COUNTERS = ("route_counts", "select_counts", "index_loss")


def select_hist_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """``[layers, blocks]`` histograms of one step."""
    prog, ref = np.asarray(prog, np.int64), np.asarray(ref, np.int64)
    return float(np.max(np.sum(np.abs(prog - ref), axis=-1)
                        / np.sum(ref, axis=-1)))


class SparseCell(TorsoCell):
    def __init__(self, env: RunEnv):
        super().__init__(env)
        self.last_select = None
        inner = self.loop.run

        def run(state, n, on_chunk=None):
            state, m = inner(state, n, on_chunk=on_chunk)
            self.last_select = m["select_counts"]
            return state, m

        self.loop.run = run

    def first_chunk(self) -> None:
        import jax
        import jax.numpy as jnp

        self.state, m = self.loop.run(self.state, self.k)
        st = self.state
        self.first = {
            "device": jax.device_get({  # on the host, as TorsoCell's
                "critic_mu": st.critic_opt_state[0].mu,
                "actor_mu": st.actor_opt_state[0].mu,
                "critic": st.critic_params, "actor": st.actor_params,
                "sum_tree": self.buffer.trees.sum_tree}),
            "metrics": {k: np.asarray(m[k]) for k in (
                "critic_loss", "actor_loss", "td_error", "idx") + COUNTERS},
            "size": int(self.buffer.size),
        }
        self._stage("first chunk run and copied to the host")
        if self.env.fault == "nan_loss":
            self.state = self.state._replace(
                critic_params=jax.tree_util.tree_map(
                    lambda x: x * jnp.nan, self.state.critic_params))

    def follow_reference(self, ops=None) -> dict:
        import jax
        import jax.numpy as jnp

        env, cfg, config = self.env, self.env.cfg, self.config
        lr = cfg["learner"]
        idx_all = self.first["metrics"]["idx"]
        s = jnp.uint32(env.seed32)
        spec = cellbuild.row_spec(cfg, config)
        seeded = jax.jit(lambda s: seeded_params(config, s))
        mirror = reference.PriorityMirror(
            np.asarray(cellbuild.seeded_p_alpha(cfg, env.seed32)),
            lr["per_alpha"], lr["per_beta0"], int(lr["per_beta_steps"]))
        make_rows = jax.jit(lambda s, idx: datagen.rows(jnp, s, idx, spec))

        def feed(t):
            obs, action, reward, nxt, _done, discount = make_rows(
                s, jnp.asarray(idx_all[t]))
            return idx_all[t], (obs, action, reward, nxt, discount)

        ref, st = reference_sparse.follow(
            cfg["model"], ops or reference_sparse.EXACT_OPS,
            reference_sparse.init(*seeded(s)), jax.random.key(s), feed,
            mirror, self.k)
        kept = {"critic_mu": st["cm"], "actor_mu": st["am"],
                "critic": st["critic"], "actor": st["actor"]}
        del st  # targets and second moments: nobody reads them
        actor0, critic0 = seeded(s)
        ref.update(kept, critic0=critic0, actor0=actor0)
        return ref

    def compare(self, prog: dict, ref: dict) -> dict:
        out = super().compare(prog, ref)
        out["select_hist_gap"] = select_hist_gap(prog["select_counts"][0],
                                                 ref["select_counts"][0])
        out["index_loss_gap"] = float(np.max(
            np.abs(prog["index_loss"] - ref["index_loss"])
            / np.abs(ref["index_loss"])))
        return out

    def control_numbers(self) -> dict:
        """The fp8 control against the exact reference; the control's
        result waits on the host while the exact one is computed."""
        import jax

        control = jax.device_get(
            self.follow_reference(reference_sparse.LOWP_OPS))
        return self.compare(control, self.follow_reference())


CELL = SparseCell  # benchmark/tools/calibrate_cell.py reads it


def run(env: RunEnv) -> dict:
    cell = SparseCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    torso = env.cfg["model"]["torso"]
    last_route, last_select = (np.asarray(cell.last_route),
                               np.asarray(cell.last_select))
    lo, hi = torso["experts_held"]
    batch = int(env.cfg["learner"]["batch_size"])
    first = cell.first["metrics"]
    for what, route, select in (
            ("first", first["route_counts"], first["select_counts"]),
            ("last", last_route, last_select)):
        env.log(f"[counter] {what} chunk: assignments to the held experts by "
                f"step and layer {route[..., lo:hi].sum(-1).tolist()} of "
                f"{int(route[0, 0].sum())} a layer; busiest held expert over "
                f"their mean "
                f"{shapes_sparse.load_max_over_mean(torso, route):.3f}; "
                f"selections by step and layer {select.sum(-1).tolist()}, "
                f"{shapes_sparse.kept_share(torso, select, batch):.4f} % of "
                f"the causal pairs; the busiest block of keys "
                f"{int(select.max())}, the idlest {int(select.min())}")
    env.log(f"[counter] index_loss by step, first chunk "
            f"{first['index_loss'].tolist()}")
    return report(
        cell, window, attempted=window["chunks"],
        failed=window["nonfinite_chunks"],
        layer_ctx={"sparse": torso, "route_counts": last_route,
                   "select_counts": last_select, "batch_size": batch})
