"""The plain reference for a configuration with a shared sequence torso: one
D4PG gradient step through one period of Mellum2-12B-A2.5B, in
straightforward float32 ``jax.numpy`` at ``Precision.HIGHEST``. Nothing of
the program is imported; ``benchmark/reference.py`` supplies the parts of
the step that do not change (heads, projection, Adam, priorities).

The layer, as the model's ``config.json`` gives it (``t`` is the
configuration file's ``model.torso`` block):

- RMSNorm (eps ``rms_norm_eps``, learned gain); ``q``, ``k``, ``v`` without
  bias; query head ``i`` reads key/value head ``i // group``.
- RoPE over ``head_dim`` by halves. Sliding layers: ``inv_freq_i =
  theta^(-2i/d)``. Full layers: YaRN, ``inv_freq`` blended between that and
  that over ``factor`` by a linear ramp between the dimensions that turn
  ``beta_fast`` and ``beta_slow`` times in the original length; ``cos`` and
  ``sin`` times ``attention_factor``.
- ``softmax(q k^T / sqrt(d) + mask) v``: causal, and in sliding layers only
  the last ``sliding_window`` positions. Naive masked scores, one sequence
  and one block of queries at a time so that they fit.
- Router in float32 whatever ``ops`` says (the configuration states
  float32 for it): softmax over all experts, the ``k`` largest, divided by
  their sum. Experts ``silu(h G) * (h U)`` then ``D``: a loop over the
  experts held here (``experts_held``), each applied to every token under
  a dense mask of the tokens that chose it. What absent experts would have
  added is left out.
- After the last layer RMSNorm, then the mean over positions.

Tokens are Gato's: mu-law (mu 100, M 256), clip to [-1, 1], ``bins``
uniform bins; bin ``b`` is row ``b`` of the embedding.

The step is ``reference.step``'s with the torso in it: the target torso
on ``next_obs``, the torso on ``obs`` under the critic loss, the stepped
torso on ``obs`` through a stop-gradient for the actor loss. Sequences go
through a layer one at a time and layers are rematerialised
(``jax.checkpoint``): the same numbers, in the memory one chip has.

``ops["dot"]`` / ``ops["einsum"]`` are injectable (``LOWP_OPS`` rounds
every input of a product the configuration states in bfloat16 to fp8: the
control).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark.reference import HI, LOG_EPS

QUERY_BLOCK = 512
MU, M = 100.0, 256.0  # Gato's mu-law


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


EXACT_OPS = {"dot": reference.EXACT_OPS["dot"], "einsum": _einsum}
LOWP_OPS = {
    "dot": reference.LOWP_OPS["dot"],
    "einsum": lambda spec, a, b: _einsum(spec, reference._fp8(a),
                                         reference._fp8(b)),
}


def tokenise(t: dict, values):
    v = values.astype(jnp.float32)
    v = jnp.sign(v) * jnp.log(jnp.abs(v) * MU + 1.0) / math.log(M * MU + 1.0)
    v = jnp.clip(v, -1.0, 1.0)
    bins = t.get("bins", 1024)
    b = jnp.floor((v + 1.0) * (bins / 2.0)).astype(jnp.int32)
    return jnp.clip(b, 0, bins - 1)


def inv_freq(rope: dict, d: int):
    """``(inv_freq [d / 2] float64, attention factor)``."""
    i = np.arange(d // 2, dtype=np.float64)
    theta = float(rope["rope_theta"])
    plain = theta ** (-2.0 * i / d)
    if rope["rope_type"] == "default":
        return plain, 1.0
    factor = float(rope["factor"])
    length = float(rope["original_max_position_embeddings"])

    def turns_at(r):  # the dimension that turns r times in `length`
        return d * math.log(length / (2 * math.pi * r)) / (2 * math.log(theta))

    low = min(max(math.floor(turns_at(rope["beta_fast"])), 0), d - 1)
    high = min(max(math.ceil(turns_at(rope["beta_slow"])), 0), d - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return ((1 - ramp) * plain + ramp * plain / factor,
            float(rope["attention_factor"]))


def rotate(x, rope: dict):
    """RoPE on ``x [T, heads, d]``, positions 0..T-1."""
    t_len, _h, d = x.shape
    freq, factor = inv_freq(rope, d)
    angle = jnp.arange(t_len, dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)[None, :]
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def attention(ops, q, k, v, window):
    """``q, k, v [T, heads, d]`` (keys already repeated per query head).
    A block of queries against every key under the mask, block after block
    (``lax.map``: one compiled body); a block's scores are made again in
    the backward pass so that one block's, not the sequence's, are held."""
    t_len, heads, d = q.shape
    size = min(QUERY_BLOCK, t_len)

    def block(xs):
        qb, start = xs
        s = ops["einsum"]("qhd,khd->hqk", qb, k) / math.sqrt(d)
        pos_q = start + jnp.arange(size)[:, None]
        pos_k = jnp.arange(t_len)[None, :]
        keep = pos_k <= pos_q
        if window is not None:
            keep &= pos_k > pos_q - window
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return ops["einsum"]("hqk,khd->qhd", p, v)

    out = jax.lax.map(jax.checkpoint(block), (
        q.reshape(t_len // size, size, heads, d),
        jnp.arange(0, t_len, size)))
    return out.reshape(t_len, heads, d)


def route(t: dict, h, router):
    """``(weights [T, k], experts [T, k], counts [num_experts])``."""
    p = jax.nn.softmax(jnp.dot(h, router, precision=HI), axis=-1)
    w, e = jax.lax.top_k(p, t["num_experts_per_tok"])
    if t.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    counts = jnp.stack([jnp.sum(e == j) for j in range(t["num_experts"])])
    return w, e, counts.astype(jnp.int32)


def experts(ops, t: dict, p: dict, h, w, e, held=None):
    """The part of the expert layer that the experts ``held`` (default: the
    configuration's ``experts_held``) give: expert ``lo + j`` is slice ``j``
    of the stacked matrices. One expert after another (``lax.scan``), each
    applied to every token and weighted by a dense mask of who chose it."""
    lo, hi = held if held is not None else t["experts_held"]

    def one(out, xs):
        j, gate, up, down = xs
        weight = jnp.sum(jnp.where(e == lo + j, w, 0.0), axis=-1)
        mid = jax.nn.silu(ops["dot"](h, gate)) * ops["dot"](h, up)
        return out + weight[:, None] * ops["dot"](mid, down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(hi - lo), p["gate"]["kernel"], p["up"]["kernel"],
        p["down"]["kernel"]))
    return out


def layer(ops, t: dict, p: dict, x, layer_type: str):
    """One layer on one sequence ``x [T, D]``: ``(x, counts)``."""
    t_len = x.shape[0]
    hq, hkv, d = (t["num_attention_heads"], t["num_key_value_heads"],
                  t["head_dim"])
    eps = t.get("rms_norm_eps", 1e-6)
    rope = t["rope_parameters"][layer_type]
    h = rms(x, p["attn_norm"]["scale"], eps)
    q = rotate(ops["dot"](h, p["q"]["kernel"]).reshape(t_len, hq, d), rope)
    k = rotate(ops["dot"](h, p["k"]["kernel"]).reshape(t_len, hkv, d), rope)
    v = ops["dot"](h, p["v"]["kernel"]).reshape(t_len, hkv, d)
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    window = (t["sliding_window"] if layer_type == "sliding_attention"
              else None)
    a = attention(ops, q, k, v, window).reshape(t_len, hq * d)
    x = x + ops["dot"](a, p["o"]["kernel"])
    h = rms(x, p["moe_norm"]["scale"], eps)
    w, e, counts = route(t, h, p["router"]["kernel"])
    return x + experts(ops, t, p, h, w, e), counts


def torso(ops, t: dict, params: dict, obs):
    """``obs [B, tokens] -> (latent [B, D], counts [layers, experts])``."""
    x = params["embed"]["kernel"][tokenise(t, obs)]
    counts = []
    for i, layer_type in enumerate(t["layer_types"]):
        one = jax.checkpoint(
            lambda p, xs, lt=layer_type: layer(ops, t, p, xs, lt))
        x, c = jax.checkpoint(lambda p, x, one=one: jax.lax.map(
            lambda xs: one(p, xs), x))(params[f"layer_{i}"], x)
        counts.append(jnp.sum(c, axis=0))
    x = rms(x, params["final_norm"]["scale"], t.get("rms_norm_eps", 1e-6))
    return jnp.mean(x, axis=1), jnp.stack(counts)


def step(cfg: dict, ops, st: dict, batch, w, key):
    """One gradient step; ``reference.step`` with the torso in it. ``cfg``
    is ``reference.model_cfg`` of the file's ``model`` block."""
    t = cfg["torso"]
    obs, action, reward, next_obs, discount = batch
    # the fused chunk splits off a sampling key, then the update splits
    _k_sample, key = jax.random.split(key)
    key, _sub = jax.random.split(key)
    head = lambda p, z, a: reference.critic_mlp(  # noqa: E731
        ops, p["params"]["critic"], z, a)
    latent = lambda p, x: torso(ops, t, p["params"]["torso"], x)  # noqa: E731
    pi = lambda p, z: reference.actor_mlp(ops, p["params"], z)  # noqa: E731

    z_next, _ = latent(st["t_critic"], next_obs)
    t_probs = head(st["t_critic"], z_next, pi(st["t_actor"], z_next))
    proj = jax.lax.stop_gradient(
        reference.project(cfg, t_probs, reward, discount))

    def critic_loss(p):
        z, counts = latent(p, obs)
        td = -jnp.sum(proj * jnp.log(head(p, z, action) + LOG_EPS), axis=-1)
        return jnp.mean(w * td), (td, counts)

    (c_loss, (td, counts)), c_grads = jax.value_and_grad(
        critic_loss, has_aux=True)(st["critic"])
    critic, cm, cv, count = reference.adam(
        st["critic"], c_grads, st["cm"], st["cv"], st["count"],
        cfg["lr_critic"])
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
               "route_counts": counts}
    return new, metrics, key


def init(actor, critic) -> dict:
    """``reference.init`` with the targets as copies of their own, so that
    ``follow`` can give the state's buffers up."""
    st = reference.init(actor, critic)
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    return {**st, "t_actor": copy(actor), "t_critic": copy(critic)}


def follow(cfg_model: dict, ops, st: dict, key, feed, mirror, n_steps: int):
    """``reference.follow`` for this step: ``n_steps`` from the state
    ``st`` (``init``), which is given up. Returns per-step
    metrics (host numpy) and the final state."""
    cfg = reference.model_cfg(cfg_model)
    jstep = jax.jit(lambda st, batch, w, key: step(cfg, ops, st, batch, w,
                                                   key), donate_argnums=(0,))
    out = {"critic_loss": [], "actor_loss": [], "td_error": [],
           "route_counts": []}
    for i in range(n_steps):
        idx, batch = feed(i)
        w = jnp.asarray(mirror.is_weights(idx, i))
        st, metrics, key = jstep(st, batch, w, key)
        td = np.asarray(metrics["td_error"])
        mirror.write_back(idx, td)
        out["critic_loss"].append(float(metrics["critic_loss"]))
        out["actor_loss"].append(float(metrics["actor_loss"]))
        out["td_error"].append(td)
        out["route_counts"].append(np.asarray(metrics["route_counts"]))
    return {k: np.asarray(v) for k, v in out.items()}, st
