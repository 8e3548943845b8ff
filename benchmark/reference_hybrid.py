"""The plain reference for a configuration whose torso is LFM2-8B-A1B's
layers (``model.torso`` with ``name`` ``lfm2``): one D4PG gradient step in
straightforward float32 ``jax.numpy`` at ``Precision.HIGHEST``. Nothing of
the program is imported; ``benchmark/reference.py`` supplies the parts of
the step that do not change (heads, projection, Adam, priorities),
``benchmark/reference_torso.py`` the tokeniser, RMSNorm, RoPE, the dense
masked attention a block of queries at a time and the loop over held
experts.

The layers, as the model's ``config.json`` and Hugging Face's ``lfm2_moe``
block give them (``t`` is the configuration file's ``model.torso`` block;
one sequence ``x [T, D]``):

- every layer: ``x <- x + Op(RMSNorm(x))``, then ``x <- x + FF(RMSNorm(x))``;
  ``Op`` by ``layer_types``, ``FF`` dense for the first ``num_dense_layers``
  layers and experts after; after the last layer one RMSNorm, then the mean
  over positions.
- ``conv``: ``[b, c, u] = h W_in`` (chunks in that order); ``g = b * u``;
  ``s[t] = sum_j w[:, j] g[t - (L - 1 - j)]`` with ``g[< 0] = 0``, written
  as an explicit sum over the ``L = conv_L_cache`` taps on a zero-padded
  array; ``Op = (c * s) W_out``. No bias, no activation.
- ``full_attention``: ``q``, ``k``, ``v`` without bias; RMSNorm with a
  learned gain over every head of ``q`` and ``k``; RoPE by halves; query
  head ``i`` reads key/value head ``i // group``; causal softmax at
  ``head_dim ** -0.5``; ``Wo``.
- dense ``FF``: ``(silu(h W1) * (h W3)) W2``.
- expert ``FF``: ``s = sigmoid(h Wr)`` in float32 whatever ``ops`` says;
  the ``k`` largest of ``s + bias`` are selected and weigh in by ``s`` (not
  by ``s + bias``), divided by their sum + 1e-6, times
  ``routed_scaling_factor``; the experts held here (``experts_held``), a
  block of ``EXPERT_BLOCK`` tokens at a time so that a sequence of 8,192
  fits. What absent experts would have added is left out.

Training: ``reference_torso.step``'s three passes. The bias has no gradient
(it enters a top-k only) and Adam leaves it; after the critic's Adam step
``bias <- bias + bias_update_rate * sign(mean(n) - n)`` with ``n`` the
differentiated pass's assignments a layer over all experts; the target's
bias follows by the Polyak average like any leaf.

``ops["dot"]`` / ``ops["einsum"]`` are injectable (``LOWP_OPS`` rounds
every input of a product the configuration states in bfloat16 to fp8: the
control); the gates and taps are elementwise and stay exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark import reference_torso as rt
from benchmark.reference import HI, LOG_EPS
from benchmark.reference_torso import EXACT_OPS, LOWP_OPS  # noqa: F401

EXPERT_BLOCK = 4096
COUNTERS = ("route_counts", "bias_swapped")


def short_conv(g, taps):
    """``s[t] = sum_j taps[:, j] g[t - (L - 1 - j)]`` on ``g [T, C]``,
    ``taps [C, L]``: ``L - 1`` rows of zeros in front, then tap ``j`` reads
    the padded array from row ``j``."""
    t_len, n_taps = g.shape[0], taps.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((n_taps - 1, g.shape[1]), g.dtype), g], axis=0)
    s = jnp.zeros_like(g)
    for j in range(n_taps):
        s = s + taps[:, j][None, :] * padded[j:j + t_len]
    return s


def conv_op(ops, p: dict, h):
    d = h.shape[1]
    bcu = ops["dot"](h, p["in_proj"]["kernel"])
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    return ops["dot"](c * short_conv(b * u, p["conv"]["kernel"]),
                      p["out_proj"]["kernel"])


def attention_op(ops, t: dict, p: dict, h):
    t_len = h.shape[0]
    hq, hkv, d = (t["num_attention_heads"], t["num_key_value_heads"],
                  t["head_dim"])
    eps = t["rms_norm_eps"]
    rope = t["rope_parameters"]["full_attention"]
    q = ops["dot"](h, p["q"]["kernel"]).reshape(t_len, hq, d)
    k = ops["dot"](h, p["k"]["kernel"]).reshape(t_len, hkv, d)
    v = ops["dot"](h, p["v"]["kernel"]).reshape(t_len, hkv, d)
    q = rt.rotate(rt.rms(q, p["q_norm"]["scale"], eps), rope)
    k = rt.rotate(rt.rms(k, p["k_norm"]["scale"], eps), rope)
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    a = rt.attention(ops, q, k, v, None).reshape(t_len, hq * d)
    return ops["dot"](a, p["o"]["kernel"])


def dense_ff(ops, p: dict, h):
    mid = jax.nn.silu(ops["dot"](h, p["w1"]["kernel"])) \
        * ops["dot"](h, p["w3"]["kernel"])
    return ops["dot"](mid, p["w2"]["kernel"])


def route(t: dict, h, router: dict):
    """``(weights [T, k], experts [T, k], counts [num_experts], swapped)``:
    ``swapped`` counts the assignments that are in the top ``k`` of score +
    bias and not in the top ``k`` of the score."""
    k, n_exp = t["num_experts_per_tok"], t["num_experts"]
    s = jax.nn.sigmoid(jnp.dot(h, router["kernel"], precision=HI))
    _, e = jax.lax.top_k(s + router["bias"], k)
    _, plain = jax.lax.top_k(s, k)
    w = jnp.take_along_axis(s, e, axis=-1)
    if t.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * t.get("routed_scaling_factor", 1.0)
    chosen = jnp.sum(jax.nn.one_hot(e, n_exp), axis=1)  # [T, experts] 0/1
    unbiased = jnp.sum(jax.nn.one_hot(plain, n_exp), axis=1)
    counts = jnp.sum(chosen, axis=0).astype(jnp.int32)
    swapped = jnp.sum(chosen * (1.0 - unbiased)).astype(jnp.int32)
    return w, e, counts, swapped


def experts(ops, t: dict, p: dict, h, w, e):
    """``reference_torso.experts`` a block of tokens at a time (the layer
    works token by token), each block's intermediates made again in the
    backward pass."""
    t_len = h.shape[0]
    size = EXPERT_BLOCK if t_len % EXPERT_BLOCK == 0 else t_len
    part = jax.checkpoint(
        lambda xs: rt.experts(ops, t, p, xs[0], xs[1], xs[2]))
    blocks = lambda a: a.reshape(t_len // size, size, a.shape[-1])  # noqa
    return jax.lax.map(part, (blocks(h), blocks(w), blocks(e))).reshape(
        h.shape)


def layer(ops, t: dict, p: dict, x, layer_type: str, dense: bool):
    """One layer on one sequence ``x [T, D]``: ``(x, (counts, swapped))``,
    ``()`` of a dense layer."""
    eps = t["rms_norm_eps"]
    if layer_type == "conv":
        x = x + conv_op(ops, p, rt.rms(x, p["conv_norm"]["scale"], eps))
    else:
        x = x + attention_op(ops, t, p,
                             rt.rms(x, p["attn_norm"]["scale"], eps))
    if dense:
        return x + dense_ff(ops, p, rt.rms(x, p["mlp_norm"]["scale"],
                                           eps)), ()
    h = rt.rms(x, p["moe_norm"]["scale"], eps)
    w, e, counts, swapped = route(t, h, p["router"])
    return x + experts(ops, t, p, h, w, e), (counts, swapped)


def torso(ops, t: dict, params: dict, obs):
    """``obs [B, tokens] -> (latent [B, D], counts [expert layers,
    experts], swapped [expert layers])``."""
    x = params["embed"]["kernel"][rt.tokenise(t, obs)]
    counts, swapped = [], []
    for i, layer_type in enumerate(t["layer_types"]):
        dense = i < t.get("num_dense_layers", 0)
        one = jax.checkpoint(lambda p, xs, lt=layer_type, dense=dense: layer(
            ops, t, p, xs, lt, dense))
        x, stats = jax.checkpoint(lambda p, x, one=one: jax.lax.map(
            lambda xs: one(p, xs), x))(params[f"layer_{i}"], x)
        if stats:
            counts.append(jnp.sum(stats[0], axis=0))
            swapped.append(jnp.sum(stats[1], axis=0))
    x = rt.rms(x, params["final_norm"]["scale"], t["rms_norm_eps"])
    return jnp.mean(x, axis=1), jnp.stack(counts), jnp.stack(swapped)


def balance(t: dict, critic: dict, counts):
    """The load-balancing rule on every expert layer's bias."""
    layers = dict(critic["params"]["torso"])
    first = t.get("num_dense_layers", 0)
    for row, i in enumerate(range(first, len(t["layer_types"]))):
        n = counts[row].astype(jnp.float32)
        lay = layers[f"layer_{i}"]
        bias = lay["router"]["bias"] + t["bias_update_rate"] * jnp.sign(
            jnp.mean(n) - n)
        layers[f"layer_{i}"] = {**lay, "router": {**lay["router"],
                                                  "bias": bias}}
    return {**critic, "params": {**critic["params"], "torso": layers}}


def step(cfg: dict, ops, st: dict, batch, w, key):
    """One gradient step; ``reference_torso.step`` with this torso in it and
    the bias rule after the critic's Adam step."""
    t = cfg["torso"]
    obs, action, reward, next_obs, discount = batch
    # the fused chunk splits off a sampling key, then the update splits
    _k_sample, key = jax.random.split(key)
    key, _sub = jax.random.split(key)
    head = lambda p, z, a: reference.critic_mlp(  # noqa: E731
        ops, p["params"]["critic"], z, a)
    latent = lambda p, x: torso(ops, t, p["params"]["torso"], x)  # noqa: E731
    pi = lambda p, z: reference.actor_mlp(ops, p["params"], z)  # noqa: E731

    z_next = latent(st["t_critic"], next_obs)[0]
    t_probs = head(st["t_critic"], z_next, pi(st["t_actor"], z_next))
    proj = jax.lax.stop_gradient(
        reference.project(cfg, t_probs, reward, discount))

    def critic_loss(p):
        z, counts, swapped = latent(p, obs)
        td = -jnp.sum(proj * jnp.log(head(p, z, action) + LOG_EPS), axis=-1)
        return jnp.mean(w * td), (td, counts, swapped)

    (c_loss, (td, counts, swapped)), c_grads = jax.value_and_grad(
        critic_loss, has_aux=True)(st["critic"])
    critic, cm, cv, count = reference.adam(
        st["critic"], c_grads, st["cm"], st["cv"], st["count"],
        cfg["lr_critic"])
    critic = balance(t, critic, counts)
    z = jax.lax.stop_gradient(latent(critic, obs)[0])

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
    metrics = {"critic_loss": c_loss, "actor_loss": a_loss, "td_error": td,
               "route_counts": counts, "bias_swapped": swapped}
    return new, metrics, key


init = rt.init


def follow(cfg_model: dict, ops, st: dict, key, feed, mirror, n_steps: int):
    """``reference_torso.follow`` for this step: ``n_steps`` from the state
    ``st`` (``init``), which is given up. Returns per-step metrics (host
    numpy) and the final state."""
    cfg = reference.model_cfg(cfg_model)
    jstep = jax.jit(lambda st, batch, w, key: step(cfg, ops, st, batch, w,
                                                   key), donate_argnums=(0,))
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
