"""``learner_static`` for a configuration whose model has a shared sequence
torso (``model.torso``): the same set-up, window and numbers, with the check
against ``benchmark/reference_torso.py``.

What differs from ``LearnerCell``, and why:

- **weights.** ``datagen.weights`` scales a ``kernel`` by the product of all
  its leading dimensions; a stack of expert matrices ``[experts, in, out]``
  and the embedding ``[rows, width]`` have their own fan-in. ``rescale``
  puts them right (expert stacks times ``sqrt(experts)``, embedding rows to
  N(0, 1)) on the state the cell built and on the reference's parameters.
- **the first chunk's copies live on the host.** ``LearnerCell`` keeps
  device copies of the critic's Adam first moments and parameters from
  set-up to the check: 4.3 GB here, beside 10.8 GB of state and the ring.
  ``compare`` only takes norms of them, so the window runs with the memory
  a deployment would have.
- **the reference** is ``reference_torso.follow``; its state is given up
  step by step and only what ``compare`` reads is kept.
- **route_hist_gap**: the chunk reports how many assignments each expert
  got (``route_counts [K, layers, experts]``); the first step's histograms
  are compared with the reference's, the largest difference over layers as
  a share of the layer's assignments.
- the last chunk's ``route_counts`` go to the per-layer readers
  (``expert_load_max_over_mean``, ``experts_roofline``).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import (
    cellbuild,
    datagen,
    reference,
    reference_torso,
    shapes_torso,
)
from benchmark.learner import LearnerCell, RunEnv, report


def rescale(tree):
    """Seeded leaves of a torso tree at their own fan-in (module docstring).
    A tree without such leaves (the heads) comes back as it is."""
    import jax

    def fix(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        if names[-1] != "kernel" or not ("embed" in names or x.ndim == 3):
            return x
        return x * math.sqrt(x.shape[0])

    return jax.tree_util.tree_map_with_path(fix, tree)


def seeded_params(config, seed32):
    actor, critic = cellbuild.seeded_params(config, seed32)
    return rescale(actor), rescale(critic)


def route_hist_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """``[layers, experts]`` histograms of one step."""
    prog, ref = np.asarray(prog, np.int64), np.asarray(ref, np.int64)
    return float(np.max(np.sum(np.abs(prog - ref), axis=-1)
                        / np.sum(ref, axis=-1)))


class TorsoCell(LearnerCell):
    def __init__(self, env: RunEnv):
        import jax

        super().__init__(env)
        fix = jax.jit(lambda st: st._replace(
            critic_params=rescale(st.critic_params),
            target_critic_params=rescale(st.target_critic_params)),
            donate_argnums=(0,))
        self.state = fix(self.state)
        self._stage("expert stacks and embedding rescaled")
        self.last_route = None
        inner = self.loop.run

        def run(state, n, on_chunk=None):
            state, m = inner(state, n, on_chunk=on_chunk)
            self.last_route = m["route_counts"]
            return state, m

        self.loop.run = run

    def first_chunk(self) -> None:
        import jax
        import jax.numpy as jnp

        self.state, m = self.loop.run(self.state, self.k)
        st = self.state
        self.first = {
            "device": jax.device_get({  # on the host: module docstring
                "critic_mu": st.critic_opt_state[0].mu,
                "actor_mu": st.actor_opt_state[0].mu,
                "critic": st.critic_params, "actor": st.actor_params,
                "sum_tree": self.buffer.trees.sum_tree}),
            "metrics": {k: np.asarray(m[k]) for k in (
                "critic_loss", "actor_loss", "td_error", "idx",
                "route_counts")},
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

        ref, st = reference_torso.follow(
            cfg["model"], ops or reference_torso.EXACT_OPS,
            reference_torso.init(*seeded(s)), jax.random.key(s), feed,
            mirror, self.k)
        kept = {"critic_mu": st["cm"], "actor_mu": st["am"],
                "critic": st["critic"], "actor": st["actor"]}
        del st  # targets and second moments: 6.5 GB nobody reads
        actor0, critic0 = seeded(s)
        ref.update(kept, critic0=critic0, actor0=actor0)
        return ref

    def compare(self, prog: dict, ref: dict) -> dict:
        out = super().compare(prog, ref)
        out["route_hist_gap"] = route_hist_gap(prog["route_counts"][0],
                                               ref["route_counts"][0])
        return out

    def control_numbers(self) -> dict:
        """The fp8 control against the exact reference; the control's
        result waits on the host while the exact one is computed."""
        import jax

        control = jax.device_get(
            self.follow_reference(reference_torso.LOWP_OPS))
        return self.compare(control, self.follow_reference())


CELL = TorsoCell  # benchmark/tools/calibrate_cell.py reads it


def run(env: RunEnv) -> dict:
    cell = TorsoCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    torso = env.cfg["model"]["torso"]
    last = np.asarray(cell.last_route)
    lo, hi = torso["experts_held"]
    for what, counts in (("first", cell.first["metrics"]["route_counts"]),
                         ("last", last)):
        env.log(f"[counter] {what} chunk: assignments to the held experts by "
                f"step and layer {counts[..., lo:hi].sum(-1).tolist()} of "
                f"{int(counts[0, 0].sum())} a layer; busiest held expert "
                f"over their mean "
                f"{shapes_torso.load_max_over_mean(torso, counts):.3f}")
    return report(
        cell, window, attempted=window["chunks"],
        failed=window["nonfinite_chunks"],
        layer_ctx={"torso": torso, "route_counts": last, "batch_size": int(
            env.cfg["learner"]["batch_size"])})
