"""``learner_static_torso`` for a configuration whose torso is Ouro's looped
layers (``model.torso`` with ``name`` ``ouro``: a dense decoder stack with
sandwich norms run ``total_ut_steps`` times on its own output, an exit gate a
pass, the expected-exit loss): the same set-up, window and numbers, with the
check against ``benchmark/reference_loop.py``.

What differs from ``TorsoCell``, and why:

- **no experts.** A dense torso reports no ``route_counts``, so the cell is a
  ``LearnerCell`` with ``TorsoCell``'s parts that still hold: the embedding
  rows rescaled to N(0, 1) (``learner_static_torso.rescale``; every other
  leaf is at its own fan-in as ``datagen.weights`` draws it: the gate's
  ``[2048, 1]`` kernel N(0, 1 / 2048), its bias 0, every gain 1), and the
  first chunk's copies kept on the host.
- **the reference** is ``reference_loop.follow``.
- **exit_dist_gap**, **loss_by_pass_gap**: the chunk's two counters (the mean
  exit distribution and the weighted TD loss a pass, both of the
  differentiated pass) against the reference's, the largest relative
  difference over steps and passes.
- **embed_moment_gap**: the norm of the difference of the embedding's Adam
  first moment over the reference's norm. ``moment_gap`` compares norms leaf
  by leaf; this one number also sees a gradient of the right size that points
  the wrong way, on the leaf that every pass's backward ends in.
- **two controls** (``control_numbers``; ``benchmark/tools/
  calibrate_controls.py`` reads them): ``fp8``, the reference with fp8
  product inputs in the program's place, as in the other cells; ``detach``,
  the reference with a stop-gradient between passes: its forward numbers are
  the sound ones and only what the backward across passes feeds differs, so a
  loop whose backward stops at a pass's edge cannot pass. Each must exceed at
  least one limit at every seed.
- the last chunk's ``exit_dist`` goes to the readers under the ``loop`` key of
  their context: ``benchmark/loop_trace.py`` reads this cell, and the other
  torso cells' readers (``torso``, ``sparse``, ``hybrid``, ``linear``) find
  nothing.
"""

from __future__ import annotations

import numpy as np

from benchmark import cellbuild, datagen, reference, reference_loop
from benchmark.drivers import learner_static_torso as torso_driver
from benchmark.learner import LearnerCell, RunEnv, report

COUNTERS = reference_loop.COUNTERS
seeded_params = torso_driver.seeded_params


def counter_gap(prog, ref) -> float:
    """The largest relative difference of a float counter ``[K, R]``."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def embed_moment_gap(prog_mu: dict, ref_mu: dict) -> float:
    """``|mu_prog - mu_ref| / |mu_ref|`` of the embedding's first moment, of
    two critic trees of moments."""
    leaf = lambda t: np.asarray(  # noqa: E731
        t["params"]["torso"]["embed"]["kernel"], np.float64)
    p, r = leaf(prog_mu), leaf(ref_mu)
    return float(np.linalg.norm(p - r) / np.linalg.norm(r))


class LoopCell(LearnerCell):
    def __init__(self, env: RunEnv):
        import jax

        super().__init__(env)
        fix = jax.jit(lambda st: st._replace(
            critic_params=torso_driver.rescale(st.critic_params),
            target_critic_params=torso_driver.rescale(
                st.target_critic_params)), donate_argnums=(0,))
        self.state = fix(self.state)
        self._stage("embedding rescaled")
        self.last_exit = self.last_loss = None
        self.exact = None  # the exact reference on the host, once followed
        inner = self.loop.run

        def run(state, n, on_chunk=None):
            state, m = inner(state, n, on_chunk=on_chunk)
            self.last_exit, self.last_loss = (m["exit_dist"],
                                              m["loss_by_pass"])
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

    def follow_reference(self, ops=None, detach: bool = False) -> dict:
        import jax
        import jax.numpy as jnp

        if ops is None and not detach and self.exact is not None:
            return self.exact
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

        ref, st = reference_loop.follow(
            cfg["model"], ops or reference_loop.EXACT_OPS,
            reference_loop.init(*seeded(s)), jax.random.key(s), feed,
            mirror, self.k, detach)
        kept = {"critic_mu": st["cm"], "actor_mu": st["am"],
                "critic": st["critic"], "actor": st["actor"]}
        del st  # targets and second moments: nobody reads them
        actor0, critic0 = seeded(s)
        ref.update(kept, critic0=critic0, actor0=actor0)
        return ref

    def compare(self, prog: dict, ref: dict) -> dict:
        out = super().compare(prog, ref)
        out["embed_moment_gap"] = embed_moment_gap(prog["critic_mu"],
                                                   ref["critic_mu"])
        for name in COUNTERS:
            out[name + "_gap"] = counter_gap(prog[name], ref[name])
        return out

    def control_numbers(self) -> dict:
        """Both controls against the exact reference, which waits on the
        host while each is computed (and for ``check_first_chunk``, if it
        comes after): ``{"fp8": numbers, "detach": numbers}``."""
        import jax

        exact = self.exact = jax.device_get(self.follow_reference())
        return {
            "fp8": self.compare(
                self.follow_reference(reference_loop.LOWP_OPS), exact),
            "detach": self.compare(
                self.follow_reference(detach=True), exact)}


CELL = LoopCell  # benchmark/tools/calibrate_controls.py reads it


def run(env: RunEnv) -> dict:
    cell = LoopCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    first = cell.first["metrics"]
    last_exit = np.asarray(cell.last_exit)
    env.log(f"[counter] first chunk: mean exit distribution by step and pass "
            f"{np.round(first['exit_dist'], 5).tolist()} (sums "
            f"{np.sum(first['exit_dist'], axis=-1).tolist()}); weighted TD "
            f"loss by step and pass "
            f"{np.round(first['loss_by_pass'], 5).tolist()}")
    env.log(f"[counter] last chunk: mean exit distribution by step and pass "
            f"{np.round(last_exit, 5).tolist()}; weighted TD loss by step "
            f"and pass {np.round(cell.last_loss, 5).tolist()}")
    return report(
        cell, window, attempted=window["chunks"],
        failed=window["nonfinite_chunks"],
        layer_ctx={"loop": env.cfg["model"]["torso"], "exit_dist": last_exit,
                   "batch_size": int(env.cfg["learner"]["batch_size"])})
