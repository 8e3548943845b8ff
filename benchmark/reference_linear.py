"""The plain reference for a configuration whose torso is Qwen3-Next-80B-A3B's
layers (``model.torso`` with ``name`` ``qwen3next``): one D4PG gradient step
in straightforward float32 ``jax.numpy`` at ``Precision.HIGHEST``. Nothing of
the program is imported; ``benchmark/reference.py`` supplies the parts of the
step that do not change (heads, projection, Adam, priorities),
``benchmark/reference_torso.py`` the tokeniser, RMSNorm, RoPE and the dense
masked attention a block of queries at a time and the loop over held experts,
``benchmark/reference_hybrid.py`` the convolution as an explicit sum over taps
on a padded array.

The layers, as the model's ``config.json`` and Hugging Face's ``qwen3_next``
give them (``t`` is the configuration file's ``model.torso`` block; one
sequence ``x [T, D]``):

- every layer: ``x <- x + Op(RMSNorm(x))``, then ``x <- x + FF(RMSNorm(x))``;
  ``Op`` by ``layer_types``, ``FF`` the expert layer in every layer; after the
  last layer one RMSNorm, then the mean over positions. A norm's gain is
  stored as ``scale = 1 + w``.
- ``linear_attention`` (Gated DeltaNet, arXiv:2412.06464): ``[q, k, v, z] = h
  W_qkvz`` (contiguous chunks in that order), ``[b, a] = h W_ba``; ``[q, k, v]
  <- silu(conv([q, k, v]))``, a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps, zeros before position 0, no bias; a head:
  ``q <- q / |q|``, ``k <- k / |k|`` (eps 1e-6 under the root), ``q <- q
  Dk^-1/2``; key head ``i`` serves value heads ``2 i, 2 i + 1``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``. **The recurrence
  is run token by token** (``delta_scan``: a ``lax.scan`` over the tokens)::

      S' = exp(g_t) S;  d_t = beta_t (v_t - S'^T k_t);
      S_t = S' + k_t d_t^T;  o_t = S_t^T q_t

  from ``S = 0`` at the row's first token, rematerialised ``SCAN_BLOCK``
  tokens at a time so that its gradient fits (a saved state a block, not a
  token). ``y = RMSNorm(o) gain silu(z)`` a value head, ``Op = y W_out``.
  The recurrence, ``g`` and ``beta`` are float32 whatever ``ops`` says (the
  configuration states float32 for them).
- ``full_attention`` (gated): a head's ``2 head_dim`` outputs of ``Wq`` are
  its query, then its gate; RMSNorm with a gain over every head of ``q`` and
  ``k``; RoPE by halves on the first ``partial_rotary_factor`` of a head
  (frequencies over that many dimensions), the rest as it is; query head
  ``i`` reads key/value head ``i // group``; causal softmax at
  ``head_dim ** -0.5``; ``Op = (attn * sigmoid(gate)) Wo``.
- expert ``FF``: ``softmax(h Wr)`` over all experts in float32 whatever
  ``ops`` says, the ``k`` largest renormalised; the experts held here
  (``experts_held``); what absent experts would have added is left out.
  Added to it, whole: ``sigmoid(h w_s) (silu(h G) * (h U)) D``, the shared
  expert under its scalar gate.

Training: ``reference_torso.step``'s three passes; every leaf is trained by
the critic loss and there is no auxiliary loss. The step also hands back
``delta_kept`` (the mean of ``exp(g)`` a DeltaNet layer) and ``shared_gate``
(the mean of ``sigmoid(h w_s)`` a layer) of the differentiated pass.

Two controls. ``LOWP_OPS`` rounds every input of a product the configuration
states in bfloat16 to fp8. ``reset_every`` zeroes the recurrence's state at
every so many tokens: a scan whose memory does not cross a chunk's edge,
which the comparison must refuse.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark import reference_hybrid as rh
from benchmark import reference_torso as rt
from benchmark.reference import HI, LOG_EPS
from benchmark.reference_torso import EXACT_OPS, LOWP_OPS  # noqa: F401

SCAN_BLOCK = 64  # tokens, and blocks of tokens, rematerialised together
QUERY_BLOCK = 256  # queries scored against every key at a time
EXPERT_BLOCK = 1024  # tokens the held experts are applied to at a time
HEAD_GROUPS = 4  # groups of key heads a DeltaNet operator is computed in
COUNTERS = ("route_counts", "delta_kept", "shared_gate")


def delta_scan(q, k, v, g, beta, reset_every=None):
    """The recurrence of the module docstring on ``q, k [T, Hk, Dk]``, ``v [T,
    H, Dv]``, ``g, beta [T, H]``: ``o [T, H, Dv]``, one token a step; key
    head ``i`` serves the ``H / Hk`` value heads from ``i H / Hk`` on.
    Rematerialised at two levels (``SCAN_BLOCK`` tokens inside
    ``SCAN_BLOCK ** 2``): the states kept are one an outer block and, inside
    the one outer block being differentiated, one an inner block."""
    t_len, key_heads, dk = q.shape
    heads, dv = v.shape[1:]
    inner = SCAN_BLOCK if t_len % SCAN_BLOCK == 0 else t_len
    outer = t_len // inner
    outer = SCAN_BLOCK if outer % SCAN_BLOCK == 0 else outer

    def token(state, xs):
        q, k, v, g, beta, at = xs
        q, k = (jnp.repeat(a, heads // key_heads, axis=0) for a in (q, k))
        if reset_every:
            state = jnp.where(at % reset_every == 0, 0.0, state)
        state = jnp.exp(g)[:, None, None] * state
        d = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k,
                                            precision=HI))
        state = state + k[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q, precision=HI)

    over = lambda f: jax.checkpoint(  # noqa: E731
        lambda state, xs: jax.lax.scan(f, state, xs))
    blocks = lambda a: a.reshape(  # noqa: E731
        (-1, outer, inner) + a.shape[1:])
    _, o = jax.lax.scan(
        over(over(token)), jnp.zeros((heads, dk, dv), jnp.float32), tuple(
            blocks(a) for a in (q, k, v, g, beta, jnp.arange(t_len))))
    return o.reshape(t_len, heads, dv)


def deltanet_op(ops, t: dict, p: dict, h, reset_every=None):
    """``(Op(h) [T, D], mean of exp(g))``. The heads are independent up to
    ``out_proj``'s sum, so the operator is a loop over ``HEAD_GROUPS`` groups
    of key heads with their value heads (``lax.scan``: one group after
    another, its part of ``out_proj`` added to the sum): that group's
    columns of ``W_qkvz``, rows of the taps and of ``W_out``. A group is
    made again in the backward pass, so the float32 ``[T, 8192]`` behind the
    taps and its gradient never stand whole."""
    t_len = h.shape[0]
    hk, hv = t["linear_num_key_heads"], t["linear_num_value_heads"]
    dk, dv = t["linear_key_head_dim"], t["linear_value_head_dim"]
    wk, wv = hk * dk, hv * dv
    n = math.gcd(hk, HEAD_GROUPS)
    w_in, taps = p["in_proj_qkvz"]["kernel"], p["conv"]["kernel"]
    ba = ops["dot"](h, p["in_proj_ba"]["kernel"])
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]["value"]) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"]["value"])
    unit = lambda a: a / jnp.sqrt(  # noqa: E731
        jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
    columns = lambda w: jnp.moveaxis(  # noqa: E731
        w.reshape(w.shape[0], n, -1), 1, 0)
    rows = lambda w: w.reshape((n, -1) + w.shape[1:])  # noqa: E731

    def group(acc, xs):
        w_q, w_k, w_v, w_z, t_q, t_k, t_v, g, beta, w_out = xs
        part = lambda w, taps: jax.nn.silu(  # noqa: E731
            rh.short_conv(ops["dot"](h, w), taps))
        q = unit(part(w_q, t_q).reshape(t_len, -1, dk)) / math.sqrt(dk)
        k = unit(part(w_k, t_k).reshape(t_len, -1, dk))
        v = part(w_v, t_v).reshape(t_len, -1, dv)
        z = ops["dot"](h, w_z).reshape(t_len, -1, dv)
        o = delta_scan(q, k, v, g, beta, reset_every)
        y = rt.rms(o, p["out_norm"]["scale"], t["rms_norm_eps"]) \
            * jax.nn.silu(z)
        return acc + ops["dot"](y.reshape(t_len, -1), w_out), None

    out, _ = jax.lax.scan(jax.checkpoint(group), jnp.zeros_like(h), (
        columns(w_in[:, :wk]), columns(w_in[:, wk:2 * wk]),
        columns(w_in[:, 2 * wk:2 * wk + wv]), columns(w_in[:, 2 * wk + wv:]),
        rows(taps[:wk]), rows(taps[wk:2 * wk]), rows(taps[2 * wk:]),
        columns(g), columns(beta), rows(p["out_proj"]["kernel"])))
    return out, jnp.mean(jnp.exp(g))


def partial_rotate(x, rope: dict, turned: int):
    """RoPE on the first ``turned`` of ``x [T, heads, d]`` (by halves of
    those: element ``i`` with ``i + turned / 2``), the rest as it is."""
    return jnp.concatenate([rt.rotate(x[..., :turned], rope),
                            x[..., turned:]], axis=-1)


def attention(ops, q, k, v):
    """Causal softmax attention of ``q [T, Hkv, G, d]`` on ``k, v [T, Hkv,
    d]``: dense masked scores, ``QUERY_BLOCK`` queries against every key at a
    time (``lax.map``), a block's scores made again in the backward pass."""
    t_len, hkv, group, d = q.shape
    size = QUERY_BLOCK if t_len % QUERY_BLOCK == 0 else t_len

    def block(xs):
        qb, start = xs
        s = ops["einsum"]("qhgd,khd->hgqk", qb, k) / math.sqrt(d)
        keep = jnp.arange(t_len)[None, :] \
            <= start + jnp.arange(size)[:, None]
        prob = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return ops["einsum"]("hgqk,khd->qhgd", prob, v)

    out = jax.lax.map(jax.checkpoint(block), (
        q.reshape(t_len // size, size, hkv, group, d),
        jnp.arange(0, t_len, size)))
    return out.reshape(t_len, hkv * group, d)


def attention_op(ops, t: dict, p: dict, h):
    """A loop over the key/value heads (``lax.scan``), each with the query
    heads that read it and its rows of ``Wo``, one made again in the backward
    pass: query head ``i`` reads key/value head ``i // (hq / hkv)``."""
    t_len = h.shape[0]
    hq, hkv, d = (t["num_attention_heads"], t["num_key_value_heads"],
                  t["head_dim"])
    eps = t["rms_norm_eps"]
    rope = t["rope_parameters"]["full_attention"]
    turned = int(d * t["partial_rotary_factor"])
    columns = lambda w: jnp.moveaxis(  # noqa: E731
        w.reshape(w.shape[0], hkv, -1), 1, 0)

    def head(acc, xs):
        w_q, w_k, w_v, w_o = xs
        qg = ops["dot"](h, w_q).reshape(t_len, hq // hkv, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        k = ops["dot"](h, w_k).reshape(t_len, 1, d)
        v = ops["dot"](h, w_v).reshape(t_len, 1, d)
        q = partial_rotate(rt.rms(q, p["q_norm"]["scale"], eps), rope, turned)
        k = partial_rotate(rt.rms(k, p["k_norm"]["scale"], eps), rope, turned)
        a = attention(ops, q[:, None], k, v) * jax.nn.sigmoid(gate)
        return acc + ops["dot"](a.reshape(t_len, -1), w_o), None

    out, _ = jax.lax.scan(jax.checkpoint(head), jnp.zeros_like(h), (
        columns(p["q"]["kernel"]), columns(p["k"]["kernel"]),
        columns(p["v"]["kernel"]),
        p["o"]["kernel"].reshape(hkv, -1, h.shape[1])))
    return out


def route(t: dict, h, router):
    """``(weights [T, k], experts [T, k], counts [num_experts])``: softmax
    over all experts in float32, the ``k`` largest, divided by their sum."""
    p = jax.nn.softmax(jnp.dot(h, router, precision=HI), axis=-1)
    w, e = jax.lax.top_k(p, t["num_experts_per_tok"])
    if t.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    counts = jnp.zeros((t["num_experts"],), jnp.int32).at[
        e.reshape(-1)].add(1)
    return w, e, counts


def experts(ops, t: dict, p: dict, h, w, e):
    """``reference_torso.experts`` (one held expert after another, each
    applied to every token under a dense mask of who chose it) a block of
    ``EXPERT_BLOCK`` tokens at a time, each block's intermediates made again
    in the backward pass: with 16 experts held, what the loop keeps an expert
    and token is 16 x 2048 floats."""
    t_len = h.shape[0]
    size = EXPERT_BLOCK if t_len % EXPERT_BLOCK == 0 else t_len
    part = jax.checkpoint(
        lambda xs: rt.experts(ops, t, p, xs[0], xs[1], xs[2]))
    blocks = lambda a: a.reshape(t_len // size, size, a.shape[-1])  # noqa
    return jax.lax.map(part, (blocks(h), blocks(w), blocks(e))).reshape(
        h.shape)


def shared_expert(ops, p: dict, h):
    """``(sigmoid(h w_s) * SwiGLU(h) [T, D], mean of the gate)``."""
    gate = jax.nn.sigmoid(ops["dot"](h, p["shared_expert_gate"]["kernel"]))
    mid = jax.nn.silu(ops["dot"](h, p["shared_gate"]["kernel"])) \
        * ops["dot"](h, p["shared_up"]["kernel"])
    return gate * ops["dot"](mid, p["shared_down"]["kernel"]), jnp.mean(gate)


def layer(ops, t: dict, p: dict, x, layer_type: str, reset_every=None):
    """One layer on one sequence ``x [T, D]``: ``(x, (counts, kept,
    shared))``; ``kept`` is 0 of an attention layer. The operator and the
    feed-forward are each made again in the backward pass, so that one's
    intermediates do not stand beside the other's."""
    eps = t["rms_norm_eps"]

    @jax.checkpoint
    def operator(p, x):
        if layer_type == "linear_attention":
            return deltanet_op(ops, t, p, rt.rms(
                x, p["linear_norm"]["scale"], eps), reset_every)
        return attention_op(ops, t, p, rt.rms(
            x, p["attn_norm"]["scale"], eps)), jnp.zeros((), jnp.float32)

    @jax.checkpoint
    def feed_forward(p, x):
        h = rt.rms(x, p["moe_norm"]["scale"], eps)
        w, e, counts = route(t, h, p["router"]["kernel"])
        alike, shared = shared_expert(ops, p, h)
        return experts(ops, t, p, h, w, e) + alike, counts, shared

    out, kept = operator(p, x)
    x = x + out
    out, counts, shared = feed_forward(p, x)
    return x + out, (counts, kept, shared)


def torso(ops, t: dict, params: dict, obs, reset_every=None):
    """``obs [B, tokens] -> (latent [B, D], counts [layers, experts], kept
    [linear layers], shared [layers])``."""
    x = params["embed"]["kernel"][rt.tokenise(t, obs)]
    counts, kept, shared = [], [], []
    for i, layer_type in enumerate(t["layer_types"]):
        one = jax.checkpoint(lambda p, xs, lt=layer_type: layer(
            ops, t, p, xs, lt, reset_every))
        x, (c, kp, sh) = jax.checkpoint(lambda p, x, one=one: jax.lax.map(
            lambda xs: one(p, xs), x))(params[f"layer_{i}"], x)
        counts.append(jnp.sum(c, axis=0))
        shared.append(jnp.mean(sh))
        if layer_type == "linear_attention":
            kept.append(jnp.mean(kp))
    x = rt.rms(x, params["final_norm"]["scale"], t["rms_norm_eps"])
    return (jnp.mean(x, axis=1), jnp.stack(counts), jnp.stack(kept),
            jnp.stack(shared))


def _parts(cfg: dict, ops, reset_every):
    t = cfg["torso"]
    head = lambda p, z, a: reference.critic_mlp(  # noqa: E731
        ops, p["params"]["critic"], z, a)
    latent = lambda p, x: torso(  # noqa: E731
        ops, t, p["params"]["torso"], x, reset_every)
    pi = lambda p, z: reference.actor_mlp(ops, p["params"], z)  # noqa: E731
    return head, latent, pi


def target(cfg: dict, ops, st: dict, batch, reset_every=None):
    """The first pass: the target networks' distribution of the next row,
    projected onto the support."""
    head, latent, pi = _parts(cfg, ops, reset_every)
    _obs, _action, reward, next_obs, discount = batch
    z_next = latent(st["t_critic"], next_obs)[0]
    t_probs = head(st["t_critic"], z_next, pi(st["t_actor"], z_next))
    return jax.lax.stop_gradient(
        reference.project(cfg, t_probs, reward, discount))


def critic_grads(cfg: dict, ops, critic: dict, batch, w, proj,
                 reset_every=None):
    """The second pass, differentiated: ``(gradients, metrics)``."""
    head, latent, _pi = _parts(cfg, ops, reset_every)
    obs, action = batch[:2]

    def critic_loss(p):
        z, counts, kept, shared = latent(p, obs)
        td = -jnp.sum(proj * jnp.log(head(p, z, action) + LOG_EPS), axis=-1)
        return jnp.mean(w * td), (td, counts, kept, shared)

    (c_loss, (td, counts, kept, shared)), grads = jax.value_and_grad(
        critic_loss, has_aux=True)(critic)
    return grads, {"critic_loss": c_loss, "td_error": td,
                   "route_counts": counts, "delta_kept": kept,
                   "shared_gate": shared}


def critic_adam(cfg: dict, st: dict, grads: dict) -> dict:
    """The critic's Adam step on the state."""
    critic, cm, cv, count = reference.adam(
        st["critic"], grads, st["cm"], st["cv"], st["count"],
        cfg["lr_critic"])
    return {**st, "critic": critic, "cm": cm, "cv": cv, "count": count}


def actor_update(cfg: dict, ops, st: dict, count, batch, reset_every=None):
    """The third pass through the stepped critic, the actor's Adam step
    (``count`` the step count before this step) and both target averages:
    ``(state, actor loss)``."""
    head, latent, pi = _parts(cfg, ops, reset_every)
    critic = st["critic"]
    z = jax.lax.stop_gradient(latent(critic, batch[0])[0])

    def actor_loss(p):
        probs = head(critic, z, pi(p, z))
        return -jnp.mean(jnp.sum(probs * reference.atoms(cfg), axis=-1))

    a_loss, a_grads = jax.value_and_grad(actor_loss)(st["actor"])
    actor, am, av, _ = reference.adam(st["actor"], a_grads, st["am"],
                                      st["av"], count, cfg["lr_actor"])
    tau = cfg["tau"]
    soft = lambda t_, o: jax.tree_util.tree_map(  # noqa: E731
        lambda a, b: (1 - tau) * a + tau * b, t_, o)
    return {**st, "actor": actor, "am": am, "av": av,
            "t_actor": soft(st["t_actor"], actor),
            "t_critic": soft(st["t_critic"], critic)}, a_loss


def step(cfg: dict, ops, st: dict, batch, w, key, reset_every=None):
    """One gradient step; ``reference_torso.step`` with this torso in it,
    in its three passes."""
    # the fused chunk splits off a sampling key, then the update splits
    _k_sample, key = jax.random.split(key)
    key, _sub = jax.random.split(key)
    proj = target(cfg, ops, st, batch, reset_every)
    grads, metrics = critic_grads(cfg, ops, st["critic"], batch, w, proj,
                                  reset_every)
    new, a_loss = actor_update(cfg, ops, critic_adam(cfg, st, grads),
                               st["count"], batch, reset_every)
    return new, {**metrics, "actor_loss": a_loss}, key


init = rt.init


PARKED = ("t_critic", "cm", "cv")  # what the differentiated pass leaves alone


def follow(cfg_model: dict, ops, st: dict, key, feed, mirror, n_steps: int,
           reset_every=None):
    """``reference_torso.follow`` for this step: ``n_steps`` from the state
    ``st`` (``init``), which is given up. Returns per-step metrics (host
    numpy) and the final state. ``key`` is the program's; the step draws
    nothing from it.

    The step's passes are programs of their own, and while the gradient is
    taken the target torso and both Adam moments (``PARKED``, 4.6 GB at the
    cell's size) wait on the host: the differentiated pass of two
    16,384-token sequences then has the chip to itself beside the critic."""
    del key
    cfg = reference.model_cfg(cfg_model)
    first = jax.jit(lambda st, batch: target(cfg, ops, st, batch,
                                             reset_every))
    second = jax.jit(lambda critic, batch, w, proj: critic_grads(
        cfg, ops, critic, batch, w, proj, reset_every))
    adam = jax.jit(lambda st, grads: critic_adam(cfg, st, grads),
                   donate_argnums=(0,))
    third = jax.jit(lambda st, count, batch: actor_update(
        cfg, ops, st, count, batch, reset_every), donate_argnums=(0,))
    out = {name: [] for name in (
        "critic_loss", "actor_loss", "td_error") + COUNTERS}
    for i in range(n_steps):
        idx, batch = feed(i)
        w = jnp.asarray(mirror.is_weights(idx, i))
        proj = first(st, batch)
        parked = jax.device_get({name: st.pop(name) for name in PARKED})
        grads, metrics = second(st["critic"], batch, w, proj)
        jax.block_until_ready(grads)
        st.update(jax.device_put(parked))
        del parked
        count = jnp.copy(st["count"])  # the state is given up before its use
        st, metrics["actor_loss"] = third(adam(st, grads), count, batch)
        del grads
        mirror.write_back(idx, np.asarray(metrics["td_error"]))
        for name in out:
            out[name].append(np.asarray(metrics[name]))
    return {k: np.asarray(v) for k, v in out.items()}, st
