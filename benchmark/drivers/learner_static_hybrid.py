"""``learner_static_torso`` for a configuration whose torso is LFM2's layers
(``model.torso`` with ``name`` ``lfm2``: short convolutions round one
attention layer, a dense layer, a sigmoid router with a load-balancing
bias): the same set-up, window and numbers, with the check against
``benchmark/reference_hybrid.py``.

What differs from ``TorsoCell``, and why:

- **weights.** ``datagen.weights`` scales a ``kernel`` by the product of its
  leading dimensions and zeroes every leaf that is neither ``kernel`` nor
  ``scale``. ``TorsoCell`` puts the expert stacks and the embedding right;
  ``finish`` does the same for the taps (``[channels, 3]``: their fan-in is
  the 3 taps, not the 2,048 channels) and seeds the routing biases: whole
  multiples of ``bias_update_rate`` uniform within ``seeded_bias_steps`` of
  zero, so that the first step's selection already differs from the
  unbiased one. The same function makes the reference's parameters.
- **the reference** is ``reference_hybrid.follow``.
- **bias_gap**: the share of the expert layers' biases (4 layers x 32 here)
  whose change over the chunk differs from the reference's. The rule moves a
  bias by the sign of mean load minus its expert's load, so a step can
  differ only where an expert's count is within the histograms' gap of the
  mean.
- the last chunk's ``bias_swapped`` goes to ``bias_swapped_share``, its
  ``route_counts`` to the expert readers, under the ``hybrid`` key of the
  readers' context: ``benchmark/hybrid_trace.py`` reads this cell, and the
  other torso cells' readers (``torso``, ``sparse``) find nothing.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import (
    cellbuild,
    datagen,
    reference,
    reference_hybrid,
    shapes_hybrid,
)
from benchmark.drivers import learner_static_torso as torso_driver
from benchmark.drivers.learner_static_torso import TorsoCell
from benchmark.learner import RunEnv, report

COUNTERS = reference_hybrid.COUNTERS
BIAS = 7  # datagen's field ids end at 6: a hash stream of the biases' own


def _names(path) -> list:
    return [str(getattr(k, "key", k)) for k in path]


def finish(tree, seed32, gamma: float, steps: int):
    """Taps at their own fan-in and seeded biases (module docstring) on a
    tree ``torso_driver.rescale`` has been over. A tree without such leaves
    (the heads) comes back as it is."""
    import jax
    import jax.numpy as jnp

    def fix(path, x):
        names = _names(path)
        if names[-2:] == ["conv", "kernel"]:
            return x * math.sqrt(x.shape[0] / x.shape[1])
        if names[-2:] == ["router", "bias"]:
            layer = int(names[-3].split("_")[1])
            u = datagen.uniform(jnp, seed32, BIAS, jnp.asarray([layer]),
                                x.shape[0])[0]
            return gamma * (jnp.floor(u * (2 * steps + 1)) - steps)
        return x

    return jax.tree_util.tree_map_with_path(fix, tree)


def seeded_params(cfg: dict, config, seed32):
    t = cfg["model"]["torso"]
    actor, critic = torso_driver.seeded_params(config, seed32)
    return actor, finish(critic, seed32, t["bias_update_rate"],
                         int(cfg["seeded_bias_steps"]))


def biases(critic: dict) -> dict:
    """layer name -> its routing bias, of a critic tree."""
    return {name: np.asarray(layer["router"]["bias"], np.float64)
            for name, layer in critic["params"]["torso"].items()
            if "bias" in layer.get("router", {})}


def bias_gap(prog: dict, ref: dict, start: dict, gamma: float) -> float:
    """The share of the biases (``biases`` of three trees) whose change from
    ``start`` is another number of ``gamma`` steps than the reference's."""
    differ = total = 0
    for name, b0 in start.items():
        steps_p = np.round((prog[name] - b0) / gamma)
        steps_r = np.round((ref[name] - b0) / gamma)
        differ += int(np.sum(steps_p != steps_r))
        total += b0.size
    return differ / total


class HybridCell(TorsoCell):
    def __init__(self, env: RunEnv):
        import jax
        import jax.numpy as jnp

        super().__init__(env)
        t = env.cfg["model"]["torso"]
        fin = lambda tree, s: finish(  # noqa: E731
            tree, s, t["bias_update_rate"],
            int(env.cfg["seeded_bias_steps"]))
        fix = jax.jit(lambda st, s: st._replace(
            critic_params=fin(st.critic_params, s),
            target_critic_params=fin(st.target_critic_params, s)),
            donate_argnums=(0,))
        self.state = fix(self.state, jnp.uint32(env.seed32))
        self._stage("taps rescaled, biases seeded")
        self.last_swapped = None
        inner = self.loop.run

        def run(state, n, on_chunk=None):
            state, m = inner(state, n, on_chunk=on_chunk)
            self.last_swapped = m["bias_swapped"]
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
        seeded = jax.jit(lambda s: seeded_params(cfg, config, s))
        mirror = reference.PriorityMirror(
            np.asarray(cellbuild.seeded_p_alpha(cfg, env.seed32)),
            lr["per_alpha"], lr["per_beta0"], int(lr["per_beta_steps"]))
        make_rows = jax.jit(lambda s, idx: datagen.rows(jnp, s, idx, spec))

        def feed(t):
            obs, action, reward, nxt, _done, discount = make_rows(
                s, jnp.asarray(idx_all[t]))
            return idx_all[t], (obs, action, reward, nxt, discount)

        ref, st = reference_hybrid.follow(
            cfg["model"], ops or reference_hybrid.EXACT_OPS,
            reference_hybrid.init(*seeded(s)), jax.random.key(s), feed,
            mirror, self.k)
        kept = {"critic_mu": st["cm"], "actor_mu": st["am"],
                "critic": st["critic"], "actor": st["actor"]}
        del st  # targets and second moments: nobody reads them
        actor0, critic0 = seeded(s)
        ref.update(kept, critic0=critic0, actor0=actor0)
        return ref

    def compare(self, prog: dict, ref: dict) -> dict:
        out = super().compare(prog, ref)
        out["bias_gap"] = bias_gap(
            biases(prog["critic"]), biases(ref["critic"]),
            biases(ref["critic0"]),
            self.env.cfg["model"]["torso"]["bias_update_rate"])
        return out

    def control_numbers(self) -> dict:
        """The fp8 control against the exact reference; the control's
        result waits on the host while the exact one is computed."""
        import jax

        control = jax.device_get(
            self.follow_reference(reference_hybrid.LOWP_OPS))
        return self.compare(control, self.follow_reference())


CELL = HybridCell  # benchmark/tools/calibrate_cell.py reads it


def run(env: RunEnv) -> dict:
    cell = HybridCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    torso = env.cfg["model"]["torso"]
    last_route, last_swapped = (np.asarray(cell.last_route),
                                np.asarray(cell.last_swapped))
    lo, hi = torso["experts_held"]
    batch = int(env.cfg["learner"]["batch_size"])
    first = cell.first["metrics"]
    for what, route, swapped in (
            ("first", first["route_counts"], first["bias_swapped"]),
            ("last", last_route, last_swapped)):
        env.log(f"[counter] {what} chunk: assignments to the held experts by "
                f"step and layer {route[..., lo:hi].sum(-1).tolist()} of "
                f"{int(route[0, 0].sum())} a layer; busiest held expert over "
                f"their mean "
                f"{shapes_hybrid.load_max_over_mean(torso, route):.3f}; "
                f"assignments the bias changed by step and layer "
                f"{swapped.tolist()}, "
                f"{shapes_hybrid.swapped_share(torso, swapped, batch):.3f} %")
    return report(
        cell, window, attempted=window["chunks"],
        failed=window["nonfinite_chunks"],
        layer_ctx={"hybrid": torso, "route_counts": last_route,
                   "bias_swapped": last_swapped, "batch_size": batch})
