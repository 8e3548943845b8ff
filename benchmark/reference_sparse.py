"""The plain reference for a configuration whose torso selects the keys its
attention reads: one D4PG gradient step through Keye-VL-2.0-30B-A3B's
language-model layers, in straightforward float32 ``jax.numpy`` at
``Precision.HIGHEST``. Nothing of the program is imported;
``benchmark/reference.py`` and ``benchmark/reference_torso.py`` supply what
does not change (heads, projection, Adam, priorities; tokens, RMSNorm, the
router and the held experts).

The layer on one sequence ``x [T, D]`` (``t`` is the configuration file's
``model.torso`` block, ``sa`` its ``sa_config``; what the published config
does not settle is the file's ``assumed`` list):

- ``h = RMSNorm(x)``; ``q``, ``k``, ``v`` without bias; RMSNorm with a
  learned gain over every head of ``q`` and ``k``; query head ``i`` reads
  key/value head ``i // group``.
- RoPE by halves, ``inv_freq_i = theta^(-2i/d)``. The config's
  ``mrope_section`` deals the ``d / 2`` frequencies to three position
  streams (time, height, width); ``angles`` builds the three streams: a
  stream of tokens with no image has all three equal to the position, so
  the angles are one-dimensional RoPE's (``tests/benchmark`` holds them to
  it).
- Indexer, on ``stop_gradient(h)``: ``qI = h WqI [T, Hi, Di]``, ``kI =
  LayerNorm(h WkI) [T, Di]``, ``w = h Ww [T, Hi]``, RoPE on ``qI`` and
  ``kI`` (one stream); ``I[t, s] = (Hi Di)^-1/2 sum_j w[t, j] relu(qI[t, j]
  . kI[s])``.
- Selection: ``lax.top_k`` of ``I[t, :]`` with ``-inf`` past ``t``, as a
  dense mask, and the causal mask over it (a row with fewer than ``topk``
  causal positions keeps them all; ``top_k`` breaks ties to the lower
  position; ``-0.0`` counts as ``0.0``).
- ``softmax(q k^T / sqrt(d))`` over the selected positions, times ``v``;
  ``x + a Wo``. Naive masked scores, a block of queries at a time.
- The indexer's loss: ``sum_t KL(p_t || softmax_{S_t} I[t, .])`` with
  ``p_t`` the main attention's probabilities summed over its heads and
  divided by their number, under a stop-gradient; the mean over layers,
  sequences and positions is added to the critic loss.
- Router and experts: ``reference_torso``'s, at this model's sizes, the
  experts a block of tokens at a time (memory; the same numbers).

``ops["dot"]`` / ``ops["einsum"]`` are injectable (``LOWP_OPS`` rounds every
input of a product the configuration states in bfloat16 to fp8: the
control); the router stays float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark import reference_torso as rt
from benchmark.reference import LOG_EPS
from benchmark.reference_torso import EXACT_OPS, LOWP_OPS, init  # noqa: F401

QUERY_BLOCK = 128
EXPERT_BLOCK = 2048
LAYER = "sparse_attention"


def angles(rope: dict, d: int, t_len: int):
    """``[t_len, d / 2]`` rotation angles. With ``mrope_section`` (and a
    head its sections fill) frequency ``i`` turns with the position stream
    its section names; the three streams of a token sequence are equal."""
    half = d // 2
    freq = float(rope["rope_theta"]) ** (
        -2.0 * np.arange(half, dtype=np.float64) / d)
    sections = rope.get("mrope_section")
    positions = jnp.stack([jnp.arange(t_len, dtype=jnp.float32)] * 3)
    stream = (np.repeat(np.arange(3), sections)
              if sections and sum(sections) == half
              else np.zeros(half, np.int64))
    return positions[stream].T * jnp.asarray(freq, jnp.float32)[None, :]


def rotate(x, angle):
    """RoPE by halves on ``x [T, heads, d]``."""
    d = x.shape[-1]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_norm(x, p: dict, eps: float):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["scale"] + p["bias"]


def selection(scores, start, topk: int):
    """Dense bool ``[rows, T]``: row ``r`` (position ``start + r``) keeps
    the ``topk`` largest of its scores up to its own position."""
    rows, t_len = scores.shape
    pos = start + jnp.arange(rows)[:, None]
    causal = jnp.arange(t_len)[None, :] <= pos
    ranked = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(ranked, min(topk, t_len))
    chosen = jnp.zeros((rows, t_len), bool).at[
        jnp.arange(rows)[:, None], idx].set(True)
    return chosen & causal


def attention(ops, sa: dict, q, k, v, qi, ki, w):
    """``(out [T, heads, d], counts [T / kv_chunk_size], loss)``: ``q, k, v
    [T, heads, d]`` (keys repeated per query head), ``qi [T, Hi, Di]``,
    ``ki [T, Di]``, ``w [T, Hi]``. A block of queries against every key,
    block after block; a block's scores are made again in the backward
    pass."""
    t_len, heads, d = q.shape
    size = min(QUERY_BLOCK, t_len)
    chunk = sa["kv_chunk_size"]

    def block(xs):
        qb, qib, wb, start = xs
        scores = jnp.sum(jax.nn.relu(ops["einsum"]("qhd,kd->qhk", qib, ki))
                         * wb[:, :, None], axis=1)
        chosen = selection(jax.lax.stop_gradient(scores), start, sa["topk"])
        s = ops["einsum"]("qhd,khd->hqk", qb, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
        out = ops["einsum"]("hqk,khd->qhd", p, v)
        target = jax.lax.stop_gradient(jnp.sum(p, axis=0) / heads)
        log_q = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf), -1)
        kl = jnp.sum(target * (
            jnp.log(jnp.where(target > 0, target, 1.0))
            - jnp.where(chosen, log_q, 0.0)))
        counts = jnp.sum(chosen, axis=0).reshape(-1, chunk).sum(-1)
        return out, counts.astype(jnp.int32), kl

    n = t_len // size
    out, counts, kl = jax.lax.map(jax.checkpoint(block), (
        q.reshape(n, size, heads, d), qi.reshape((n, size) + qi.shape[1:]),
        w.reshape(n, size, -1), jnp.arange(0, t_len, size)))
    return out.reshape(t_len, heads, d), jnp.sum(counts, 0), jnp.sum(kl)


def experts(ops, t: dict, p: dict, h, w, e):
    """``reference_torso.experts`` (every held expert applied to every
    token under a dense mask), ``EXPERT_BLOCK`` tokens at a time: the layer
    works token by token, and the backward pass of the scan over experts
    keeps six ``[experts, tokens, width]`` arrays (6.6 GB at 16,384 tokens),
    so a block is rematerialised on its own."""
    t_len = h.shape[0]
    size = math.gcd(EXPERT_BLOCK, t_len)
    blocks = lambda a: a.reshape((t_len // size, size) + a.shape[1:])  # noqa
    out = jax.lax.map(jax.checkpoint(
        lambda xs: rt.experts(ops, t, p, *xs)), (blocks(h), blocks(w),
                                                 blocks(e)))
    return out.reshape(h.shape)


def layer(ops, t: dict, p: dict, x):
    """One layer on one sequence ``x [T, D]``: ``(x, route counts, select
    counts, indexer loss summed over positions)``."""
    t_len = x.shape[0]
    hq, hkv, d = (t["num_attention_heads"], t["num_key_value_heads"],
                  t["head_dim"])
    sa = t["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps = t.get("rms_norm_eps", 1e-6)
    rope = t["rope_parameters"][LAYER]
    h = rt.rms(x, p["attn_norm"]["scale"], eps)
    q = ops["dot"](h, p["q"]["kernel"]).reshape(t_len, hq, d)
    k = ops["dot"](h, p["k"]["kernel"]).reshape(t_len, hkv, d)
    v = ops["dot"](h, p["v"]["kernel"]).reshape(t_len, hkv, d)
    if t.get("qk_norm", False):
        q = rt.rms(q, p["q_norm"]["scale"], eps)
        k = rt.rms(k, p["k_norm"]["scale"], eps)
    main = angles(rope, d, t_len)
    q, k = rotate(q, main), rotate(k, main)
    k, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (k, v))
    hx = jax.lax.stop_gradient(h)
    small = angles(rope, di, t_len)
    qi = rotate(ops["dot"](hx, p["index_q"]["kernel"]).reshape(
        t_len, hi, di), small)
    ki = rotate(layer_norm(ops["dot"](hx, p["index_k"]["kernel"]),
                           p["index_k_norm"], eps)[:, None, :], small)[:, 0]
    w = ops["dot"](hx, p["index_w"]["kernel"]) / math.sqrt(hi * di)
    a, selected, loss = attention(ops, sa, q, k, v, qi, ki, w)
    x = x + ops["dot"](a.reshape(t_len, hq * d), p["o"]["kernel"])
    h = rt.rms(x, p["moe_norm"]["scale"], eps)
    w, e, counts = rt.route(t, h, p["router"]["kernel"])
    return x + experts(ops, t, p, h, w, e), counts, selected, loss


def torso(ops, t: dict, params: dict, obs):
    """``obs [B, tokens] -> (latent [B, D], route counts [layers, experts],
    select counts [layers, tokens / kv_chunk_size], indexer loss)``."""
    x = params["embed"]["kernel"][rt.tokenise(t, obs)]
    counts, selected, losses = [], [], []
    for i, layer_type in enumerate(t["layer_types"]):
        if layer_type != LAYER:
            raise ValueError(f"this reference has {LAYER} layers only")
        one = jax.checkpoint(lambda p, xs: layer(ops, t, p, xs))
        x, c, s, loss = jax.checkpoint(lambda p, x, one=one: jax.lax.map(
            lambda xs: one(p, xs), x))(params[f"layer_{i}"], x)
        counts.append(jnp.sum(c, axis=0))
        selected.append(jnp.sum(s, axis=0))
        losses.append(loss)
    x = rt.rms(x, params["final_norm"]["scale"], t.get("rms_norm_eps", 1e-6))
    index_loss = jnp.mean(jnp.stack(losses)) / t["tokens"]
    return (jnp.mean(x, axis=1), jnp.stack(counts), jnp.stack(selected),
            index_loss)


def step(cfg: dict, ops, st: dict, batch, w, key):
    """One gradient step: ``reference_torso.step`` with this torso and the
    indexer's loss beside the critic loss. ``cfg`` is
    ``reference.model_cfg`` of the file's ``model`` block."""
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
        z, counts, selected, index_loss = latent(p, obs)
        td = -jnp.sum(proj * jnp.log(head(p, z, action) + LOG_EPS), axis=-1)
        loss = jnp.mean(w * td)
        return loss + index_loss, (loss, td, counts, selected, index_loss)

    (_, (c_loss, td, counts, selected, index_loss)), c_grads = \
        jax.value_and_grad(critic_loss, has_aux=True)(st["critic"])
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
               "route_counts": counts, "select_counts": selected,
               "index_loss": index_loss}
    return new, metrics, key


def follow(cfg_model: dict, ops, st: dict, key, feed, mirror, n_steps: int):
    """``reference_torso.follow`` for this step: ``n_steps`` from the state
    ``st`` (``init``), which is given up. Returns per-step metrics (host
    numpy) and the final state."""
    cfg = reference.model_cfg(cfg_model)
    jstep = jax.jit(lambda st, batch, w, key: step(cfg, ops, st, batch, w,
                                                   key), donate_argnums=(0,))
    out = {name: [] for name in ("critic_loss", "actor_loss", "td_error",
                                 "route_counts", "select_counts",
                                 "index_loss")}
    for i in range(n_steps):
        idx, batch = feed(i)
        w = jnp.asarray(mirror.is_weights(idx, i))
        st, metrics, key = jstep(st, batch, w, key)
        td = np.asarray(metrics["td_error"])
        mirror.write_back(idx, td)
        for name in out:
            out[name].append(td if name == "td_error"
                             else np.asarray(metrics[name]))
    return {k: np.asarray(v) for k, v in out.items()}, st
