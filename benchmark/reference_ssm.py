"""The plain reference for a configuration whose torso is Nemotron-H's blocks
(``model.torso`` with ``name`` ``nemotronh``; the language tower of
Nemotron-Labs-TwoTower-30B-A3B): one D4PG gradient step in straightforward
float32 ``jax.numpy``, every product at ``Precision.HIGHEST`` and the whole of
it traced under ``jax.default_matmul_precision("highest")``. Nothing of the
program is imported; ``benchmark/reference.py`` supplies the parts of the step
that do not change (heads, projection, Adam, priorities),
``benchmark/reference_torso.py`` the tokeniser and RMSNorm,
``benchmark/reference_hybrid.py`` the convolution as an explicit sum over taps
on a padded array, ``benchmark/reference_linear.py`` the dense masked
attention a block of queries at a time.

The blocks, as the model's ``config.json`` and Hugging Face's ``nemotron_h``
give them (``t`` is the configuration file's ``model.torso`` block; one
sequence ``x [T, D]``). **A block is one branch**: ``x <- x + Mixer(RMSNorm(
x))`` with the mixer named by the block's character in
``hybrid_override_pattern``; after the last block one RMSNorm, then the mean
over positions.

- ``M`` (Mamba-2, arXiv:2405.21060): ``[z | xBC | dt] = h W_in`` (contiguous,
  in that order: ``inner | inner + 2 G N | H``); ``xBC <- silu(conv(xBC) +
  b_conv)``, a depthwise causal convolution of ``conv_kernel`` taps, zeros
  before position 0; ``xBC`` splits into ``xs [T, H, P]``, ``B``, ``C [T, G,
  N]``; ``dt <- softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``.
  **The recurrence is run token by token** (``ssm_scan``: a ``lax.scan`` over
  the tokens), head ``j`` reading group ``j // (H / G)``::

      S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T;  y_t = S_t C_t + D xs_t

  from ``S = 0`` at the row's first token, rematerialised ``SCAN_BLOCK``
  tokens at a time so that its gradient fits (a saved state a block, not a
  token). Then ``y <- y * silu(z)`` (the gate BEFORE the norm), an RMSNorm
  over each of the ``G`` groups of ``inner / G`` channels times a gain a
  channel, ``Mixer = y W_out``. The recurrence, ``dt`` and the decay are
  float32 whatever ``ops`` says.
- ``*``: ``q``, ``k``, ``v``, ``o`` without bias, no norm on the heads and
  **no rotary embedding**; query head ``i`` reads key/value head ``i //
  group``; causal softmax at ``head_dim ** -0.5``.
- ``E``: ``s = sigmoid(h Wr)`` over all experts in float32 whatever ``ops``
  says; the ``k`` largest of ``s + bias`` are selected and weigh in by ``s``
  (not by ``s + bias``), divided by their sum + 1e-20 (Hugging Face's
  ``NemotronHTopkRouter``), times ``routed_scaling_factor``; a routed expert
  is ``relu(h U) ** 2 D`` (two matrices); the experts held here
  (``experts_held``), what absent experts would have added left out. Added to
  it, whole and ungated: the shared expert, the same form at its own width.

Training: ``reference_torso.step``'s three passes. The bias has no gradient
(it enters a top-k only) and Adam leaves it; after the critic's Adam step
``bias <- bias + bias_update_rate * sign(mean(n) - n)`` with ``n`` the
differentiated pass's assignments an ``E`` block over all experts; the
target's bias follows by the Polyak average like any leaf. The step also
hands back ``ssd_kept`` (the mean of ``exp(dt A)`` a Mamba block),
``route_counts`` and ``bias_swapped`` (a row an ``E`` block) of the
differentiated pass.

Two controls. ``LOWP_OPS`` rounds every input of a product the configuration
states in bfloat16 to fp8. ``reset_every`` zeroes the recurrence's state at
every so many tokens: a scan whose memory does not cross a chunk's edge,
which the comparison must refuse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark import reference_hybrid as rh
from benchmark import reference_linear as rl
from benchmark import reference_torso as rt
from benchmark.reference import HI, LOG_EPS
from benchmark.reference_torso import EXACT_OPS, LOWP_OPS  # noqa: F401

SCAN_BLOCK = 128  # tokens rematerialised together
EXPERT_BLOCK = 2048  # tokens the held experts are applied to at a time
BLOCKS = {"M": "mamba", "E": "moe", "*": "attention"}
COUNTERS = ("route_counts", "bias_swapped", "ssd_kept")


def blocks(t: dict) -> list:
    """The block kinds, from the pattern string alone."""
    return [BLOCKS[c] for c in t["hybrid_override_pattern"]]


def ssm_scan(xs, dt, a, b, c, d, reset_every=None):
    """The recurrence of the module docstring on ``xs [T, H, P]``, ``dt [T,
    H]``, ``a, d [H]``, ``b, c [T, G, N]``: ``y [T, H, P]``, one token a
    step. A block of ``SCAN_BLOCK`` tokens is made again in the backward
    pass: the states kept are one a block and, inside the block being
    differentiated, one a token."""
    t_len, heads, width = xs.shape
    per = heads // b.shape[1]
    size = SCAN_BLOCK if t_len % SCAN_BLOCK == 0 else t_len

    def token(state, at):
        x, step, bt, ct, pos = at
        bt, ct = (jnp.repeat(u, per, axis=0) for u in (bt, ct))  # [H, N]
        if reset_every:
            state = jnp.where(pos % reset_every == 0, 0.0, state)
        state = jnp.exp(step * a)[:, None, None] * state \
            + (step[:, None] * x)[:, :, None] * bt[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, ct, precision=HI)
        return state, y + d[:, None] * x

    block = jax.checkpoint(lambda state, at: jax.lax.scan(token, state, at))
    cut = lambda u: u.reshape((-1, size) + u.shape[1:])  # noqa: E731
    _, y = jax.lax.scan(
        block, jnp.zeros((heads, width, b.shape[2]), jnp.float32),
        tuple(cut(u) for u in (xs, dt, b, c, jnp.arange(t_len))))
    return y.reshape(t_len, heads, width)


def mamba_op(ops, t: dict, p: dict, h, reset_every=None):
    """``(Mixer(h) [T, D], mean of exp(dt A))``."""
    t_len = h.shape[0]
    heads, width = t["mamba_num_heads"], t["mamba_head_dim"]
    groups, n_state = t["n_groups"], t["ssm_state_size"]
    inner, bc = heads * width, groups * n_state
    zxbcdt = ops["dot"](h, p["in_proj"]["kernel"])
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * bc],
                  zxbcdt[:, 2 * inner + 2 * bc:])
    xbc = jax.nn.silu(rh.short_conv(xbc, p["conv"]["kernel"])
                      + p["conv"]["bias"][None, :])
    xs = xbc[:, :inner].reshape(t_len, heads, width)
    b = xbc[:, inner:inner + bc].reshape(t_len, groups, n_state)
    c = xbc[:, inner + bc:].reshape(t_len, groups, n_state)
    dt = jax.nn.softplus(dt + p["dt_bias"]["value"])
    a = -jnp.exp(p["A_log"]["value"])
    y = ssm_scan(xs, dt, a, b, c, p["D"]["value"], reset_every)
    y = (y.reshape(t_len, inner) * jax.nn.silu(z)).reshape(
        t_len, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                     + t["rms_norm_eps"])
    y = y.reshape(t_len, inner) * p["out_norm"]["scale"]
    return ops["dot"](y, p["out_proj"]["kernel"]), jnp.mean(jnp.exp(dt * a))


def attention_op(ops, t: dict, p: dict, h):
    """A loop over the key/value heads (``lax.scan``), each with the query
    heads that read it and its rows of ``Wo``, one made again in the backward
    pass: query head ``i`` reads key/value head ``i // (hq / hkv)``. No
    rotary embedding, no norm on the heads."""
    t_len = h.shape[0]
    hq, hkv, d = (t["num_attention_heads"], t["num_key_value_heads"],
                  t["head_dim"])
    columns = lambda w: jnp.moveaxis(  # noqa: E731
        w.reshape(w.shape[0], hkv, -1), 1, 0)

    def head(acc, xs):
        w_q, w_k, w_v, w_o = xs
        q = ops["dot"](h, w_q).reshape(t_len, 1, hq // hkv, d)
        k = ops["dot"](h, w_k).reshape(t_len, 1, d)
        v = ops["dot"](h, w_v).reshape(t_len, 1, d)
        a = rl.attention(ops, q, k, v)
        return acc + ops["dot"](a.reshape(t_len, -1), w_o), None

    out, _ = jax.lax.scan(jax.checkpoint(head), jnp.zeros_like(h), (
        columns(p["q"]["kernel"]), columns(p["k"]["kernel"]),
        columns(p["v"]["kernel"]),
        p["o"]["kernel"].reshape(hkv, -1, h.shape[1])))
    return out


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
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * t.get("routed_scaling_factor", 1.0)
    chosen = jnp.sum(jax.nn.one_hot(e, n_exp), axis=1)  # [T, experts] 0/1
    unbiased = jnp.sum(jax.nn.one_hot(plain, n_exp), axis=1)
    counts = jnp.sum(chosen, axis=0).astype(jnp.int32)
    swapped = jnp.sum(chosen * (1.0 - unbiased)).astype(jnp.int32)
    return w, e, counts, swapped


def relu2(ops, h, up, down):
    """One expert: ``relu(h U) ** 2 D``."""
    return ops["dot"](jnp.square(jax.nn.relu(ops["dot"](h, up))), down)


def experts(ops, t: dict, p: dict, h, w, e, held=None):
    """The part of the expert layer that the experts ``held`` (default: the
    configuration's ``experts_held``) give: expert ``lo + j`` is slice ``j``
    of the stacked matrices. One expert after another (``lax.scan``), each
    applied to every token and weighted by a dense mask of who chose it; a
    block of ``EXPERT_BLOCK`` tokens at a time, made again in the backward
    pass."""
    lo, hi = held if held is not None else t["experts_held"]
    t_len = h.shape[0]
    size = EXPERT_BLOCK if t_len % EXPERT_BLOCK == 0 else t_len

    def part(xs):
        hb, wb, eb = xs

        def one(out, ws):
            j, up, down = ws
            weight = jnp.sum(jnp.where(eb == lo + j, wb, 0.0), axis=-1)
            return out + weight[:, None] * relu2(ops, hb, up, down), None

        return jax.lax.scan(one, jnp.zeros_like(hb), (
            jnp.arange(hi - lo), p["up"]["kernel"], p["down"]["kernel"]))[0]

    cut = lambda u: u.reshape(t_len // size, size, u.shape[-1])  # noqa: E731
    return jax.lax.map(jax.checkpoint(part), (cut(h), cut(w), cut(e))) \
        .reshape(h.shape)


def moe_op(ops, t: dict, p: dict, h, held=None):
    """``(routed part + shared expert [T, D], counts, swapped)``."""
    w, e, counts, swapped = route(t, h, p["router"])
    shared = relu2(ops, h, p["shared_up"]["kernel"],
                   p["shared_down"]["kernel"])
    return experts(ops, t, p, h, w, e, held) + shared, counts, swapped


def block(ops, t: dict, p: dict, x, kind: str, reset_every=None):
    """One block on one sequence ``x [T, D]``: ``(x, stats)``; ``stats`` is
    ``(counts, swapped)`` of an ``E`` block, ``(kept,)`` of an ``M`` block,
    ``()`` of a ``*`` block."""
    eps = t["rms_norm_eps"]
    if kind == "mamba":
        out, kept = mamba_op(ops, t, p, rt.rms(
            x, p["mamba_norm"]["scale"], eps), reset_every)
        return x + out, (kept,)
    if kind == "attention":
        return x + attention_op(ops, t, p, rt.rms(
            x, p["attn_norm"]["scale"], eps)), ()
    out, counts, swapped = moe_op(ops, t, p, rt.rms(
        x, p["moe_norm"]["scale"], eps))
    return x + out, (counts, swapped)


def torso(ops, t: dict, params: dict, obs, reset_every=None):
    """``obs [B, tokens] -> (latent [B, D], counts [E blocks, experts],
    swapped [E blocks], kept [M blocks])``."""
    x = params["embed"]["kernel"][rt.tokenise(t, obs)]
    counts, swapped, kept = [], [], []
    for i, kind in enumerate(blocks(t)):
        one = jax.checkpoint(lambda p, xs, kind=kind: block(
            ops, t, p, xs, kind, reset_every))
        x, stats = jax.checkpoint(lambda p, x, one=one: jax.lax.map(
            lambda xs: one(p, xs), x))(params[f"layer_{i}"], x)
        if kind == "moe":
            counts.append(jnp.sum(stats[0], axis=0))
            swapped.append(jnp.sum(stats[1], axis=0))
        elif kind == "mamba":
            kept.append(jnp.mean(stats[0]))
    x = rt.rms(x, params["final_norm"]["scale"], t["rms_norm_eps"])
    return (jnp.mean(x, axis=1), jnp.stack(counts), jnp.stack(swapped),
            jnp.stack(kept))


def balance(t: dict, critic: dict, counts):
    """The load-balancing rule on every ``E`` block's bias."""
    layers = dict(critic["params"]["torso"])
    rows = [i for i, kind in enumerate(blocks(t)) if kind == "moe"]
    for row, i in enumerate(rows):
        n = counts[row].astype(jnp.float32)
        lay = layers[f"layer_{i}"]
        bias = lay["router"]["bias"] + t["bias_update_rate"] * jnp.sign(
            jnp.mean(n) - n)
        layers[f"layer_{i}"] = {**lay, "router": {**lay["router"],
                                                  "bias": bias}}
    return {**critic, "params": {**critic["params"], "torso": layers}}


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
        z, counts, swapped, kept = latent(p, obs)
        td = -jnp.sum(proj * jnp.log(head(p, z, action) + LOG_EPS), axis=-1)
        return jnp.mean(w * td), (td, counts, swapped, kept)

    (c_loss, (td, counts, swapped, kept)), grads = jax.value_and_grad(
        critic_loss, has_aux=True)(critic)
    return grads, {"critic_loss": c_loss, "td_error": td,
                   "route_counts": counts, "bias_swapped": swapped,
                   "ssd_kept": kept}


def critic_adam(cfg: dict, st: dict, grads: dict, counts) -> dict:
    """The critic's Adam step on the state, then the bias rule."""
    critic, cm, cv, count = reference.adam(
        st["critic"], grads, st["cm"], st["cv"], st["count"],
        cfg["lr_critic"])
    return {**st, "critic": balance(cfg["torso"], critic, counts), "cm": cm,
            "cv": cv, "count": count}


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


init = rt.init

PARKED = ("t_critic", "cm", "cv")  # what the differentiated pass leaves alone


def follow(cfg_model: dict, ops, st: dict, key, feed, mirror, n_steps: int,
           reset_every=None):
    """``reference_torso.follow`` for this step: ``n_steps`` from the state
    ``st`` (``init``), which is given up. Returns per-step metrics (host
    numpy) and the final state. ``key`` is the program's; the step draws
    nothing from it.

    The step's passes are programs of their own, and while the gradient is
    taken the target torso and both Adam moments (``PARKED``, 5.8 GB at the
    cell's size) wait on the host: the differentiated pass of four
    8,192-token sequences then has the chip to itself beside the critic."""
    del key
    cfg = reference.model_cfg(cfg_model)
    with jax.default_matmul_precision("highest"):
        first = jax.jit(lambda st, batch: target(cfg, ops, st, batch,
                                                 reset_every))
        second = jax.jit(lambda critic, batch, w, proj: critic_grads(
            cfg, ops, critic, batch, w, proj, reset_every))
        adam = jax.jit(lambda st, grads, counts: critic_adam(
            cfg, st, grads, counts), donate_argnums=(0,))
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
            count = jnp.copy(st["count"])  # the state is given up before use
            st, metrics["actor_loss"] = third(
                adam(st, grads, metrics["route_counts"]), count, batch)
            del grads
            mirror.write_back(idx, np.asarray(metrics["td_error"]))
            for name in out:
                out[name].append(np.asarray(metrics[name]))
    return {k: np.asarray(v) for k, v in out.items()}, st
