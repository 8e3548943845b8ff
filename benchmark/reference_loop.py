"""The plain reference for a configuration whose torso is Ouro's looped layers
(``model.torso`` with ``name`` ``ouro``: a dense decoder stack with sandwich
norms run ``total_ut_steps`` times on its own output, an exit gate a pass and
the expected-exit loss): one D4PG gradient step in straightforward float32
``jax.numpy`` at ``Precision.HIGHEST``. Nothing of the program is imported;
``benchmark/reference.py`` supplies the parts of the step that do not change
(heads, projection, Adam, priorities), ``benchmark/reference_torso.py`` the
tokeniser, RMSNorm, RoPE and the dense masked attention a block of queries at
a time, ``benchmark/reference_hybrid.py`` the dense SwiGLU.

The layers, as the model's ``config.json`` and the published
``OuroDecoderLayer`` / ``OuroModel.forward`` give them (``t`` is the
configuration file's ``model.torso`` block; one sequence ``x [T, D]``;
``Norm`` is an RMSNorm with a gain of its own at every place it is written):

- a layer: ``x <- x + Norm_2(Attn(Norm_1(x)))``, then ``x <- x +
  Norm_4(MLP(Norm_3(x)))``. ``Attn``: ``q``, ``k``, ``v`` without bias, no
  q/k norm, RoPE by halves over the whole head, query head ``i`` on key/value
  head ``i`` (no grouping), causal softmax at ``head_dim ** -0.5``, no
  window, ``Wo``. ``MLP = (silu(h W1) * (h W3)) W2``.
- the loop: for ``r = 1..R``: ``x_r = Norm_f(Layers(x_{r-1}))``, a Python
  loop over passes and layers on the ONE parameter tree; ``x_r`` is what
  pass ``r + 1`` starts from and what is pooled: ``u_r = mean_T(x_r)``.
- the exit gate, in float32 whatever ``ops`` says: ``lambda_r =
  sigmoid(w_g . u_r + b_g)``; ``p_1 = lambda_1``, ``p_r = lambda_r prod_{j<r}
  (1 - lambda_j)``, ``p_R = prod_{j<R} (1 - lambda_j)`` (``lambda_R`` is not
  read).

Training: ``reference_torso.step``'s three passes. The target torso and the
stepped torso (for the actor loss) run all ``R`` passes and read ``u_R``.
Under the critic loss ``l[r, i]`` is the categorical TD loss of the one
critic head on ``(u_r of row i, action_i)``; the loss is ``mean_i w_i sum_r
p[r, i] l[r, i] - beta mean_i H(p_i)``, ``H(p) = -sum_r p_r log p_r``,
``beta`` the block's ``exit_entropy_beta``; ``jax.grad`` runs through all of
it. ``critic_loss`` is the first term, ``td_error`` pass ``R``'s,
``exit_dist [R]`` the mean over rows of ``p``, ``loss_by_pass [R]`` the
weighted mean of ``l`` a pass.

``ops["dot"]`` / ``ops["einsum"]`` are injectable (``LOWP_OPS`` rounds every
input of a product the configuration states in bfloat16 to fp8: the first
control). ``detach=True`` puts a stop-gradient between passes (the second
control): every forward number is the sound one, and the backward of pass
``r`` stops at its own first layer, as in a loop whose gradient ends at a
pass's edge. Sequences go through a layer one at a time and layers are
rematerialised (``jax.checkpoint``): the same numbers, in the memory one chip
has.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark import reference_torso as rt
from benchmark.reference import HI, LOG_EPS
from benchmark.reference_hybrid import dense_ff
from benchmark.reference_torso import EXACT_OPS, LOWP_OPS  # noqa: F401

COUNTERS = ("exit_dist", "loss_by_pass")


def attention_op(ops, t: dict, p: dict, h):
    t_len = h.shape[0]
    heads, d = t["num_attention_heads"], t["head_dim"]
    rope = t["rope_parameters"]["full_attention"]
    q, k, v = (ops["dot"](h, p[name]["kernel"]).reshape(t_len, heads, d)
               for name in ("q", "k", "v"))
    a = rt.attention(ops, rt.rotate(q, rope), rt.rotate(k, rope), v, None)
    return ops["dot"](a.reshape(t_len, heads * d), p["o"]["kernel"])


def layer(ops, t: dict, p: dict, x):
    """One layer on one sequence ``x [T, D]``, its four norms as
    ``OuroDecoderLayer`` wires them."""
    eps = t["rms_norm_eps"]
    norm = lambda name, a: rt.rms(a, p[name]["scale"], eps)  # noqa: E731
    x = x + norm("op_post_norm", attention_op(ops, t, p, norm("attn_norm", x)))
    return x + norm("ff_post_norm", dense_ff(ops, p, norm("mlp_norm", x)))


def passes(ops, t: dict, params: dict, obs, detach: bool = False):
    """``obs [B, tokens] -> [x_1, ..., x_R]``, each ``[B, T, D]``, normed."""
    x = params["embed"]["kernel"][rt.tokenise(t, obs)]
    out = []
    for r in range(t["total_ut_steps"]):
        if detach and r:
            x = jax.lax.stop_gradient(x)
        for i in range(len(t["layer_types"])):
            one = jax.checkpoint(lambda p, xs: layer(ops, t, p, xs))
            x = jax.checkpoint(lambda p, x, one=one: jax.lax.map(
                lambda xs: one(p, xs), x))(params[f"layer_{i}"], x)
        x = rt.rms(x, params["final_norm"]["scale"], t["rms_norm_eps"])
        out.append(x)
    return out


def torso(ops, t: dict, params: dict, obs, detach: bool = False):
    """``(latents [R, B, D], gate logits [R, B])``."""
    latents = jnp.stack([jnp.mean(x, axis=1)
                         for x in passes(ops, t, params, obs, detach)])
    gate = params["exit_gate"]
    logits = jnp.dot(latents, gate["kernel"], precision=HI)[..., 0]
    return latents, logits + gate["bias"]


def exit_distribution(logits):
    """``p [R, B]`` of gate logits ``[R, B]``, by its definition."""
    lam = jax.nn.sigmoid(logits)
    left, p = jnp.ones_like(lam[0]), []
    for r in range(logits.shape[0] - 1):
        p.append(lam[r] * left)
        left = left * (1.0 - lam[r])
    return jnp.stack(p + [left])


def step(cfg: dict, ops, st: dict, batch, w, key, detach: bool = False):
    """One gradient step; ``reference_torso.step`` with the looped torso and
    the expected-exit loss in it."""
    t = cfg["torso"]
    obs, action, reward, next_obs, discount = batch
    # the fused chunk splits off a sampling key, then the update splits
    _k_sample, key = jax.random.split(key)
    key, _sub = jax.random.split(key)
    head = lambda p, z, a: reference.critic_mlp(  # noqa: E731
        ops, p["params"]["critic"], z, a)
    run = lambda p, x: torso(  # noqa: E731
        ops, t, p["params"]["torso"], x, detach)
    pi = lambda p, z: reference.actor_mlp(ops, p["params"], z)  # noqa: E731

    z_next = run(st["t_critic"], next_obs)[0][-1]
    t_probs = head(st["t_critic"], z_next, pi(st["t_actor"], z_next))
    proj = jax.lax.stop_gradient(
        reference.project(cfg, t_probs, reward, discount))

    def critic_loss(p):
        latents, logits = run(p, obs)
        td = jnp.stack([
            -jnp.sum(proj * jnp.log(head(p, z, action) + LOG_EPS), axis=-1)
            for z in latents])  # [R, B]
        p_exit = exit_distribution(logits)
        entropy = -jnp.sum(p_exit * jnp.log(p_exit), axis=0)
        first = jnp.mean(w * jnp.sum(p_exit * td, axis=0))
        total = first - t["exit_entropy_beta"] * jnp.mean(entropy)
        return total, (first, td, p_exit)

    (_, (c_loss, td, p_exit)), c_grads = jax.value_and_grad(
        critic_loss, has_aux=True)(st["critic"])
    critic, cm, cv, count = reference.adam(
        st["critic"], c_grads, st["cm"], st["cv"], st["count"],
        cfg["lr_critic"])
    z = jax.lax.stop_gradient(run(critic, obs)[0][-1])

    def actor_loss(p):
        probs = head(critic, z, pi(p, z))
        return -jnp.mean(jnp.sum(probs * reference.atoms(cfg), axis=-1))

    a_loss, a_grads = jax.value_and_grad(actor_loss)(st["actor"])
    actor, am, av, _ = reference.adam(st["actor"], a_grads, st["am"],
                                      st["av"], st["count"], cfg["lr_actor"])
    tau = cfg["tau"]
    soft = lambda t_, o: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: (1 - tau) * a + tau * b, t_, o)
    new = {"actor": actor, "critic": critic,
           "t_actor": soft(st["t_actor"], actor),
           "t_critic": soft(st["t_critic"], critic),
           "am": am, "av": av, "cm": cm, "cv": cv, "count": count}
    metrics = {"critic_loss": c_loss, "actor_loss": a_loss,
               "td_error": td[-1], "exit_dist": jnp.mean(p_exit, axis=1),
               "loss_by_pass": jnp.mean(w * td, axis=1)}
    return new, metrics, key


init = rt.init


def follow(cfg_model: dict, ops, st: dict, key, feed, mirror, n_steps: int,
           detach: bool = False):
    """``reference_torso.follow`` for this step: ``n_steps`` from the state
    ``st`` (``init``), which is given up. Returns per-step metrics (host
    numpy) and the final state."""
    cfg = reference.model_cfg(cfg_model)
    jstep = jax.jit(lambda st, batch, w, key: step(
        cfg, ops, st, batch, w, key, detach), donate_argnums=(0,))
    out = {name: [] for name in (
        "critic_loss", "actor_loss", "td_error") + COUNTERS}
    for i in range(n_steps):
        idx, batch = feed(i)
        w = jnp.asarray(mirror.is_weights(idx, i))
        st, metrics, key = jstep(st, batch, w, key)
        mirror.write_back(idx, np.asarray(metrics["td_error"]))
        for name in out:
            out[name].append(np.asarray(metrics[name]))
    return {k: np.asarray(v) for k, v in out.items()}, st
