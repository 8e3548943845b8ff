"""``learner_static_torso`` for a configuration whose torso is Qwen3-Next's
layers (``model.torso`` with ``name`` ``qwen3next``: Gated DeltaNet
linear-attention layers round one gated softmax attention, many small experts
and a gated shared expert in every layer): the same set-up, window and
numbers, with the check against ``benchmark/reference_linear.py``.

What differs from ``TorsoCell``, and why:

- **weights.** ``datagen.weights`` scales a ``kernel`` by the product of its
  leading dimensions and zeroes every leaf that is neither ``kernel`` nor
  ``scale``. ``TorsoCell`` puts the expert stacks and the embedding right;
  ``finish`` does the same for the taps (``[channels, 4]``: their fan-in is
  the 4 taps, not the 8,192 channels) and seeds the recurrence's decay, which
  zeros would turn into the same slow decay on every head: ``A ~ U(A)``,
  ``A_log = log A``; ``dt`` log-uniform on the file's ``seeded_decay.dt``,
  ``dt_bias`` its inverse softplus (Mamba-2's and the published Gated
  DeltaNet's initialisation). The same function makes the reference's
  parameters.
- **the reference** is ``reference_linear.follow``.
- **delta_kept_gap**, **shared_gate_gap**: the chunk's two new counters (the
  mean of ``exp(g)`` a DeltaNet layer, the mean of the shared expert's gate a
  layer, both of the differentiated pass) against the reference's, the
  largest relative difference over steps and layers.
- **two controls** (``control_numbers``; ``benchmark/tools/
  calibrate_controls.py`` reads them): ``fp8``, the reference with fp8
  product inputs in the program's place, as in the other cells; ``reset64``,
  the reference with the recurrence's state set to zero at every 64th token:
  a scan whose memory ends at a chunk's edge. Each must exceed at least one
  limit at every seed.
- the last chunk's ``route_counts`` and ``delta_kept`` go to the readers
  under the ``linear`` key of their context: ``benchmark/linear_trace.py``
  reads this cell, and the other torso cells' readers (``torso``, ``sparse``,
  ``hybrid``) find nothing.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import (
    cellbuild,
    datagen,
    reference,
    reference_linear,
    shapes_linear,
)
from benchmark.drivers import learner_static_torso as torso_driver
from benchmark.drivers.learner_static_torso import TorsoCell
from benchmark.learner import RunEnv, report

COUNTERS = reference_linear.COUNTERS
DECAY = 8  # datagen's field ids end at 6, the hybrid driver's bias is 7
RESET_EVERY = 64  # the second control: no memory across a chunk's edge


def _names(path) -> list:
    return [str(getattr(k, "key", k)) for k in path]


def finish(tree, seed32, decay: dict):
    """Taps at their own fan-in and a seeded decay (module docstring) on a
    tree ``torso_driver.rescale`` has been over. A tree without such leaves
    (the heads) comes back as it is."""
    import jax
    import jax.numpy as jnp

    def fix(path, x):
        names = _names(path)
        if names[-2:] == ["conv", "kernel"]:
            return x * math.sqrt(x.shape[0] / x.shape[1])
        if names[-2] in ("A_log", "dt_bias"):
            layer = int(names[-3].split("_")[1])
            u = datagen.uniform(jnp, seed32, DECAY, jnp.asarray([layer]),
                                x.shape[0], salt=int(names[-2] == "dt_bias"))[0]
            if names[-2] == "A_log":
                lo, hi = decay["A"]
                return jnp.log(jnp.maximum(lo + (hi - lo) * u, 1e-6))
            lo, hi = (math.log(v) for v in decay["dt"])
            dt = jnp.exp(lo + (hi - lo) * u)
            return dt + jnp.log(-jnp.expm1(-dt))
        return x

    return jax.tree_util.tree_map_with_path(fix, tree)


def seeded_params(cfg: dict, config, seed32):
    actor, critic = torso_driver.seeded_params(config, seed32)
    return actor, finish(critic, seed32, cfg["seeded_decay"])


def counter_gap(prog, ref) -> float:
    """The largest relative difference of a float counter ``[K, layers]``."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


class LinearCell(TorsoCell):
    def __init__(self, env: RunEnv):
        import jax
        import jax.numpy as jnp

        super().__init__(env)
        fin = lambda tree, s: finish(  # noqa: E731
            tree, s, env.cfg["seeded_decay"])
        fix = jax.jit(lambda st, s: st._replace(
            critic_params=fin(st.critic_params, s),
            target_critic_params=fin(st.target_critic_params, s)),
            donate_argnums=(0,))
        self.state = fix(self.state, jnp.uint32(env.seed32))
        self._stage("taps rescaled, decay seeded")
        self.last_kept = None
        self.exact = None  # the exact reference on the host, once followed
        inner = self.loop.run

        def run(state, n, on_chunk=None):
            state, m = inner(state, n, on_chunk=on_chunk)
            self.last_kept = m["delta_kept"]
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

    def follow_reference(self, ops=None, reset_every=None) -> dict:
        import jax
        import jax.numpy as jnp

        if ops is None and reset_every is None and self.exact is not None:
            return self.exact
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

        ref, st = reference_linear.follow(
            cfg["model"], ops or reference_linear.EXACT_OPS,
            reference_linear.init(*seeded(s)), jax.random.key(s), feed,
            mirror, self.k, reset_every)
        kept = {"critic_mu": st["cm"], "actor_mu": st["am"],
                "critic": st["critic"], "actor": st["actor"]}
        del st  # targets and second moments: nobody reads them
        actor0, critic0 = seeded(s)
        ref.update(kept, critic0=critic0, actor0=actor0)
        return ref

    def compare(self, prog: dict, ref: dict) -> dict:
        out = super().compare(prog, ref)
        out["delta_kept_gap"] = counter_gap(prog["delta_kept"],
                                            ref["delta_kept"])
        out["shared_gate_gap"] = counter_gap(prog["shared_gate"],
                                             ref["shared_gate"])
        return out

    def control_numbers(self) -> dict:
        """Both controls against the exact reference, which waits on the
        host while each is computed (and for ``check_first_chunk``, if it
        comes after): ``{"fp8": numbers, "reset64": numbers}``."""
        import jax

        exact = self.exact = jax.device_get(self.follow_reference())
        return {
            "fp8": self.compare(
                self.follow_reference(reference_linear.LOWP_OPS), exact),
            "reset64": self.compare(
                self.follow_reference(reset_every=RESET_EVERY), exact)}


CELL = LinearCell  # benchmark/tools/calibrate_controls.py reads it


def run(env: RunEnv) -> dict:
    cell = LinearCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    torso = env.cfg["model"]["torso"]
    last_route, last_kept = (np.asarray(cell.last_route),
                             np.asarray(cell.last_kept))
    lo, hi = torso["experts_held"]
    first = cell.first["metrics"]
    for what, route, kept, shared in (
            ("first", first["route_counts"], first["delta_kept"],
             first["shared_gate"]),
            ("last", last_route, last_kept, None)):
        env.log(f"[counter] {what} chunk: assignments to the held experts by "
                f"step and layer {route[..., lo:hi].sum(-1).tolist()} of "
                f"{int(route[0, 0].sum())} a layer; busiest held expert over "
                f"their mean "
                f"{shapes_linear.load_max_over_mean(torso, route):.3f}; "
                f"mean exp(g) by step and DeltaNet layer "
                f"{np.round(kept, 4).tolist()}"
                + ("" if shared is None else
                   f"; mean shared-expert gate by step and layer "
                   f"{np.round(shared, 4).tolist()}"))
    return report(
        cell, window, attempted=window["chunks"],
        failed=window["nonfinite_chunks"],
        layer_ctx={"linear": torso, "route_counts": last_route,
                   "delta_kept": last_kept, "batch_size": int(
                       env.cfg["learner"]["batch_size"])})
