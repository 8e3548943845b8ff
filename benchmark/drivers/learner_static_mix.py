"""``learner_static_torso`` for a configuration whose torso is Trinity-Mini's
layers (``model.torso`` with ``name`` ``trinity``: a gated, q/k-normed
attention that is windowed with rotary embedding on ``sliding_attention``
layers and full without it on ``full_attention`` ones, four norms a layer, a
leading dense layer, gated-SiLU experts under a sigmoid router with a
load-balancing bias and an ungated shared expert): the same set-up, window and
numbers, with the check against ``benchmark/reference_mix.py``.

What differs from ``HybridCell`` (the LFM2 cell's, whose seeded routing biases
and ``bias_gap`` this cell shares as they are), and why:

- **the reference** is ``reference_mix.follow``; the exact one is kept on the
  host once followed, so that three controls and the check share it.
- **three controls** (``control_numbers``; ``benchmark/tools/
  calibrate_controls.py`` reads them): ``fp8``, the reference with fp8 product
  inputs in the program's place, as in the other cells; ``all_full``, the
  reference whose ``sliding_attention`` layers see every earlier key (a window
  that does not cut); ``roped_full``, the reference that rotates ``q`` and
  ``k`` on the ``full_attention`` layers too (one rotary regime, not two).
  Each must exceed at least one limit at every seed.
- the last chunk's ``route_counts`` and ``bias_swapped`` go to the readers
  under the ``mix`` key of their context: ``benchmark/mix_trace.py`` reads
  this cell, and the other torso cells' readers (``torso``, ``sparse``,
  ``hybrid``, ``linear``, ``loop``, ``ssm``) find nothing.
"""

from __future__ import annotations

import numpy as np

from benchmark import cellbuild, datagen, reference, reference_mix, shapes_mix
from benchmark.drivers.learner_static_hybrid import HybridCell, seeded_params
from benchmark.learner import RunEnv, report


class MixCell(HybridCell):
    def __init__(self, env: RunEnv):
        super().__init__(env)
        self.exact = None  # the exact reference on the host, once followed

    def follow_reference(self, ops=None, control=None) -> dict:
        import jax
        import jax.numpy as jnp

        if ops is None and control is None and self.exact is not None:
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

        ref, st = reference_mix.follow(
            cfg["model"], ops or reference_mix.EXACT_OPS,
            reference_mix.init(*seeded(s)), jax.random.key(s), feed, mirror,
            self.k, control)
        kept = {"critic_mu": st["cm"], "actor_mu": st["am"],
                "critic": st["critic"], "actor": st["actor"]}
        del st  # targets and second moments: nobody reads them
        actor0, critic0 = seeded(s)
        ref.update(kept, critic0=critic0, actor0=actor0)
        return ref

    def control_numbers(self) -> dict:
        """The three controls against the exact reference, which waits on the
        host while each is computed (and for ``check_first_chunk``, if it
        comes after): ``{"fp8": numbers, "all_full": numbers, "roped_full":
        numbers}``."""
        import jax

        exact = self.exact = jax.device_get(self.follow_reference())
        return {
            "fp8": self.compare(
                self.follow_reference(reference_mix.LOWP_OPS), exact),
            "all_full": self.compare(
                self.follow_reference(control="all_full"), exact),
            "roped_full": self.compare(
                self.follow_reference(control="roped_full"), exact)}


CELL = MixCell  # benchmark/tools/calibrate_controls.py reads it


def run(env: RunEnv) -> dict:
    cell = MixCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    torso = env.cfg["model"]["torso"]
    batch = int(env.cfg["learner"]["batch_size"])
    last = {"route_counts": np.asarray(cell.last_route),
            "bias_swapped": np.asarray(cell.last_swapped)}
    lo, hi = torso["experts_held"]
    for what, m in (("first", cell.first["metrics"]), ("last", last)):
        route = m["route_counts"]
        swapped = shapes_mix.swapped_share(torso, m["bias_swapped"], batch)
        env.log(f"[counter] {what} chunk: assignments to the held experts by "
                f"step and expert layer "
                f"{route[..., lo:hi].sum(-1).tolist()} of "
                f"{int(route[0, 0].sum())} a layer; busiest held expert over "
                f"their mean {shapes_mix.load_max_over_mean(torso, route):.3f}"
                f"; assignments the bias changed by step and expert layer "
                f"{m['bias_swapped'].tolist()}, {swapped:.3f} %")
    return report(
        cell, window, attempted=window["chunks"],
        failed=window["nonfinite_chunks"],
        layer_ctx={"mix": torso, "batch_size": batch, **last})
