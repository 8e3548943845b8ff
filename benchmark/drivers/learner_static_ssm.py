"""``learner_static_torso`` for a configuration whose torso is Nemotron-H's
blocks (``model.torso`` with ``name`` ``nemotronh``: Mamba-2 mixers, an
attention block without rotary embedding, relu2 experts under a sigmoid router
with a load-balancing bias and an ungated shared expert, each block one
branch): the same set-up, window and numbers, with the check against
``benchmark/reference_ssm.py``.

What differs from ``TorsoCell``, and why:

- **weights.** ``datagen.weights`` scales a ``kernel`` by the product of its
  leading dimensions and zeroes every leaf that is neither ``kernel`` nor
  ``scale``. ``TorsoCell`` puts the expert stacks and the embedding right;
  ``finish`` does the same for the taps (``[channels, 4]``: their fan-in is
  the 4 taps, not the 6,144 channels), draws their bias (uniform within ``1 /
  sqrt(taps)``), seeds the recurrence's decay, which zeros would turn into the
  same slow decay on every head (``A ~ U(A)``, ``A_log = log A``; ``dt``
  log-uniform on the file's ``seeded_decay.dt``, ``dt_bias`` its inverse
  softplus: Mamba-2's initialisation at the config's own bounds), sets the
  skip ``D`` to ones and seeds the routing biases as the LFM2 driver does
  (whole multiples of ``bias_update_rate`` uniform within
  ``seeded_bias_steps`` of zero). The same function makes the reference's
  parameters.
- **the reference** is ``reference_ssm.follow``.
- **ssd_kept_gap**: the chunk's new counter (the mean of ``exp(dt A)`` a Mamba
  block, of the differentiated pass) against the reference's, the largest
  relative difference over steps and blocks. **bias_gap**: the share of the
  ``E`` blocks' biases whose change over the chunk differs from the
  reference's (``learner_static_hybrid.bias_gap``).
- **two controls** (``control_numbers``; ``benchmark/tools/
  calibrate_controls.py`` reads them): ``fp8``, the reference with fp8
  product inputs in the program's place, as in the other cells; ``reset128``,
  the reference with the recurrence's state set to zero at every
  ``chunk_size``-th token (128 at the cell's size): a scan whose memory ends
  at a chunk's edge. Each must exceed at least one limit at every seed.
- the last chunk's ``route_counts``, ``bias_swapped`` and ``ssd_kept`` go to
  the readers under the ``ssm`` key of their context:
  ``benchmark/ssm_trace.py`` reads this cell, and the other torso cells'
  readers (``torso``, ``sparse``, ``hybrid``, ``linear``, ``loop``) find
  nothing.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import cellbuild, datagen, reference, reference_ssm, shapes_ssm
from benchmark.drivers import learner_static_torso as torso_driver
from benchmark.drivers.learner_static_hybrid import BIAS, bias_gap, biases
from benchmark.drivers.learner_static_linear import DECAY, counter_gap
from benchmark.drivers.learner_static_torso import TorsoCell
from benchmark.learner import RunEnv, report

COUNTERS = reference_ssm.COUNTERS
TAP_BIAS = 9  # datagen's field ids end at 6; 7 the biases', 8 the decay's


def _names(path) -> list:
    return [str(getattr(k, "key", k)) for k in path]


def finish(tree, seed32, cfg: dict):
    """The leaves ``datagen.weights`` cannot seed (module docstring) on a
    tree ``torso_driver.rescale`` has been over. A tree without such leaves
    (the heads) comes back as it is."""
    import jax
    import jax.numpy as jnp

    torso = cfg["model"]["torso"]
    gamma, taps = torso["bias_update_rate"], torso["conv_kernel"]
    steps, decay = int(cfg["seeded_bias_steps"]), cfg["seeded_decay"]

    def fix(path, x):
        names = _names(path)
        if len(names) < 3 or not names[-3].startswith("layer_"):
            return x
        layer = jnp.asarray([int(names[-3].split("_")[1])])
        draw = lambda field, salt=0: datagen.uniform(  # noqa: E731
            jnp, seed32, field, layer, x.shape[0], salt=salt)[0]
        if names[-2:] == ["conv", "kernel"]:
            return x * math.sqrt(x.shape[0] / x.shape[1])
        if names[-2:] == ["conv", "bias"]:
            return (2.0 * draw(TAP_BIAS) - 1.0) / math.sqrt(taps)
        if names[-2:] == ["router", "bias"]:
            return gamma * (jnp.floor(draw(BIAS) * (2 * steps + 1)) - steps)
        if names[-2] == "D":
            return jnp.ones_like(x)
        if names[-2] == "A_log":
            lo, hi = decay["A"]
            return jnp.log(lo + (hi - lo) * draw(DECAY))
        if names[-2] == "dt_bias":
            lo, hi = (math.log(v) for v in decay["dt"])
            dt = jnp.exp(lo + (hi - lo) * draw(DECAY, 1))
            return dt + jnp.log(-jnp.expm1(-dt))
        return x

    return jax.tree_util.tree_map_with_path(fix, tree)


def seeded_params(cfg: dict, config, seed32):
    actor, critic = torso_driver.seeded_params(config, seed32)
    return actor, finish(critic, seed32, cfg)


class SsmCell(TorsoCell):
    def __init__(self, env: RunEnv):
        import jax
        import jax.numpy as jnp

        super().__init__(env)
        fix = jax.jit(lambda st, s: st._replace(
            critic_params=finish(st.critic_params, s, env.cfg),
            target_critic_params=finish(st.target_critic_params, s, env.cfg)),
            donate_argnums=(0,))
        self.state = fix(self.state, jnp.uint32(env.seed32))
        self._stage("taps rescaled; their bias, decay and biases seeded")
        self.last = {}
        self.exact = None  # the exact reference on the host, once followed
        inner = self.loop.run

        def run(state, n, on_chunk=None):
            state, m = inner(state, n, on_chunk=on_chunk)
            self.last = {name: m[name] for name in COUNTERS}
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

        ref, st = reference_ssm.follow(
            cfg["model"], ops or reference_ssm.EXACT_OPS,
            reference_ssm.init(*seeded(s)), jax.random.key(s), feed,
            mirror, self.k, reset_every)
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
        out["ssd_kept_gap"] = counter_gap(prog["ssd_kept"], ref["ssd_kept"])
        return out

    def control_numbers(self) -> dict:
        """Both controls against the exact reference, which waits on the
        host while each is computed (and for ``check_first_chunk``, if it
        comes after): ``{"fp8": numbers, "reset128": numbers}``."""
        import jax

        exact = self.exact = jax.device_get(self.follow_reference())
        every = int(self.env.cfg["model"]["torso"]["chunk_size"])
        return {
            "fp8": self.compare(
                self.follow_reference(reference_ssm.LOWP_OPS), exact),
            "reset128": self.compare(
                self.follow_reference(reset_every=every), exact)}


CELL = SsmCell  # benchmark/tools/calibrate_controls.py reads it


def run(env: RunEnv) -> dict:
    cell = SsmCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    torso = env.cfg["model"]["torso"]
    batch = int(env.cfg["learner"]["batch_size"])
    last = {name: np.asarray(v) for name, v in cell.last.items()}
    lo, hi = torso["experts_held"]
    for what, m in (("first", cell.first["metrics"]), ("last", last)):
        route = m["route_counts"]
        swapped = shapes_ssm.swapped_share(torso, m["bias_swapped"], batch)
        env.log(f"[counter] {what} chunk: assignments to the held experts by "
                f"step and E block {route[..., lo:hi].sum(-1).tolist()} of "
                f"{int(route[0, 0].sum())} a block; busiest held expert over "
                f"their mean "
                f"{shapes_ssm.load_max_over_mean(torso, route):.3f}; "
                f"assignments the bias changed by step and E block "
                f"{m['bias_swapped'].tolist()}, "
                f"{swapped:.3f} %; mean exp(dt A) by step and Mamba block "
                f"{np.round(m['ssd_kept'], 4).tolist()}")
    return report(
        cell, window, attempted=window["chunks"],
        failed=window["nonfinite_chunks"],
        layer_ctx={"ssm": torso, "batch_size": batch, **last})
