"""The plain reference for a configuration whose torso is Trinity-Mini's layers
(``model.torso`` with ``name`` ``trinity``; Hugging Face's ``afmoe``): one
D4PG gradient step in straightforward float32 ``jax.numpy``, every product at
``Precision.HIGHEST`` and the whole of it traced under
``jax.default_matmul_precision("highest")``. Nothing of the program is
imported; ``benchmark/reference.py`` supplies the parts of the step that do
not change (heads, projection, Adam, priorities),
``benchmark/reference_torso.py`` the tokeniser, RMSNorm, the rotation by
halves and the loop over held experts, ``benchmark/reference_ssm.py`` the
sigmoid router with its bias (``route``: afmoe's and Nemotron-H's are one
function, 1e-20 in the sum).

The layer, as the model's ``config.json`` and ``modeling_afmoe.py`` give it
(``t`` is the configuration file's ``model.torso`` block; one sequence ``x [T,
D]``)::

    x0 = embedding_multiplier * Embed[token]              (mup_enabled)
    a  = x + N2(Attn(N1(x)));   y = a + N4(FF(N3(a)))     four RMSNorms a layer

after the last layer one RMSNorm, then the mean over positions.

- ``Attn``: ``q = h Wq`` (``H`` heads of ``d``), ``k = h Wk``, ``v = h Wv``
  (``Hkv`` heads), ``g = h Wg`` (``H`` heads of ``d``), no bias; RMSNorm with
  a gain ``[d]`` over each head of ``q`` and ``k``, BEFORE the rotation. A
  ``sliding_attention`` layer rotates ``q`` and ``k`` by halves (the whole
  head, ``rope_parameters["sliding_attention"]``) and key ``s`` is visible to
  query ``t`` iff ``t - sliding_window < s <= t``; a ``full_attention`` layer
  rotates NOTHING and is causal. Query head ``i`` reads key/value head ``i //
  (H / Hkv)``; softmax at ``d ** -0.5``; ``Attn = (A * sigmoid(g)) Wo``. The
  program keeps ``Wq`` and ``Wg`` in one leaf ``q [D, H, 2, d]`` (a head's
  query columns, then its gate's): ``split_gate`` takes them apart, after
  which the two are afmoe's two separate projections.
- ``FF``, the first ``num_dense_layers`` layers: ``(silu(h W1) * (h W3)) W2``.
- ``FF``, the others: ``s = sigmoid(h Wr)`` over all experts in float32
  whatever ``ops`` says; the ``k`` largest of ``s + bias`` are selected and
  weigh in by ``s`` (not by ``s + bias``), divided by their sum + 1e-20
  (afmoe's ``route_norm``), times ``routed_scaling_factor`` (``route_scale``);
  the weight multiplies the expert's OUTPUT; the experts held here
  (``experts_held``), what absent experts would have added left out. Added to
  it, whole and ungated: the shared expert, one SwiGLU at its own width.

Dense masked scores: one key/value head with its ``H / Hkv`` query heads at a
time (``lax.scan``), ``QUERY_BLOCK`` queries against EVERY key under the mask
(``lax.map``), a head and a block made again in the backward pass: blocks that
make 16,384 tokens fit, nothing else. The mask is written from the two
inequalities above, not from the program's.

Training: ``reference_torso.step``'s three passes, as ``reference_ssm.py``
splits them into programs of their own. The bias has no gradient (it enters a
top-k only) and Adam leaves it; after the critic's Adam step ``bias <- bias +
bias_update_rate * sign(mean(n) - n)`` with ``n`` the differentiated pass's
assignments a layer over all experts; the target's bias follows by the Polyak
average like any leaf.

Three controls. ``LOWP_OPS`` rounds every input of a product the
configuration states in bfloat16 to fp8. ``control="all_full"`` lets the
``sliding_attention`` layers see every earlier key (a window that does not
cut). ``control="roped_full"`` rotates ``q`` and ``k`` on the
``full_attention`` layers too, by the sliding layers' block (one rotary
regime, not two). The comparison must refuse each.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference
from benchmark import reference_torso as rt
from benchmark.reference import LOG_EPS
from benchmark.reference_ssm import route  # noqa: F401 - the same router
from benchmark.reference_torso import EXACT_OPS, LOWP_OPS  # noqa: F401

QUERY_BLOCK = 512  # queries scored against every key at a time
EXPERT_BLOCK = 4096  # tokens the held experts are applied to at a time
COUNTERS = ("route_counts", "bias_swapped")
CONTROLS = (None, "all_full", "roped_full")


def split_gate(t: dict, w):
    """``(Wq, Wg)``, each ``[D, H * d]``, of the program's one leaf ``[D, H
    * 2 * d]`` (a head's query columns, then its gate's)."""
    heads, d = t["num_attention_heads"], t["head_dim"]
    w = w.reshape(w.shape[0], heads, 2, d)
    return (w[:, :, 0, :].reshape(w.shape[0], heads * d),
            w[:, :, 1, :].reshape(w.shape[0], heads * d))


def visible(t_len: int, start, size: int, window):
    """``[size, t_len]`` bool: key ``s`` is visible to query ``t`` (``start
    <= t < start + size``) iff ``s <= t`` and, under a window, ``t - window <
    s``: the window counts the query's own position."""
    pos_q = start + jnp.arange(size)[:, None]
    pos_k = jnp.arange(t_len)[None, :]
    keep = pos_k <= pos_q
    if window is not None:
        keep &= pos_q - window < pos_k
    return keep


def attention(ops, q, k, v, window):
    """Softmax attention of ``q [T, G, d]`` (one key/value head's query
    heads) on ``k, v [T, d]`` under ``visible``: ``[T, G, d]``."""
    t_len, group, d = q.shape
    size = QUERY_BLOCK if t_len % QUERY_BLOCK == 0 else t_len

    def block(xs):
        qb, start = xs
        s = ops["einsum"]("qgd,kd->gqk", qb, k) / math.sqrt(d)
        keep = visible(t_len, start, size, window)
        prob = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return ops["einsum"]("gqk,kd->qgd", prob, v)

    out = jax.lax.map(jax.checkpoint(block), (
        q.reshape(t_len // size, size, group, d),
        jnp.arange(0, t_len, size)))
    return out.reshape(t_len, group, d)


def attention_op(ops, t: dict, p: dict, h, layer_type: str, control=None):
    """``Attn(h)`` of the module docstring, a key/value head at a time."""
    t_len = h.shape[0]
    hq, hkv, d = (t["num_attention_heads"], t["num_key_value_heads"],
                  t["head_dim"])
    group, eps = hq // hkv, t["rms_norm_eps"]
    sliding = layer_type == "sliding_attention"
    window = t["sliding_window"] if sliding and control != "all_full" \
        else None
    rope = t["rope_parameters"][layer_type]
    if rope is None and control == "roped_full":
        rope = t["rope_parameters"]["sliding_attention"]
    turn = (lambda x: x) if rope is None else (  # noqa: E731
        lambda x: rt.rotate(x, rope))
    w_q, w_g = split_gate(t, p["q"]["kernel"])
    columns = lambda w: jnp.moveaxis(  # noqa: E731
        w.reshape(w.shape[0], hkv, -1), 1, 0)

    def head(acc, xs):
        wq, wg, wk, wv, wo = xs
        q = ops["dot"](h, wq).reshape(t_len, group, d)
        g = ops["dot"](h, wg).reshape(t_len, group, d)
        k = ops["dot"](h, wk).reshape(t_len, 1, d)
        v = ops["dot"](h, wv)
        q = turn(rt.rms(q, p["q_norm"]["scale"], eps))
        k = turn(rt.rms(k, p["k_norm"]["scale"], eps))[:, 0]
        a = attention(ops, q, k, v, window) * jax.nn.sigmoid(g)
        return acc + ops["dot"](a.reshape(t_len, -1), wo), None

    out, _ = jax.lax.scan(jax.checkpoint(head), jnp.zeros_like(h), (
        columns(w_q), columns(w_g), columns(p["k"]["kernel"]),
        columns(p["v"]["kernel"]),
        p["o"]["kernel"].reshape(hkv, -1, h.shape[1])))
    return out


def swiglu(ops, h, gate, up, down):
    return ops["dot"](jax.nn.silu(ops["dot"](h, gate)) * ops["dot"](h, up),
                      down)


def experts(ops, t: dict, p: dict, h, w, e, held=None):
    """``reference_torso.experts`` (a loop over the held experts, each applied
    to every token and its OUTPUT weighted by a dense mask of who chose it) a
    block of ``EXPERT_BLOCK`` tokens at a time, made again in the backward
    pass."""
    t_len = h.shape[0]
    size = EXPERT_BLOCK if t_len % EXPERT_BLOCK == 0 else t_len
    part = jax.checkpoint(
        lambda xs: rt.experts(ops, t, p, xs[0], xs[1], xs[2], held))
    cut = lambda u: u.reshape(t_len // size, size, u.shape[-1])  # noqa: E731
    return jax.lax.map(part, (cut(h), cut(w), cut(e))).reshape(h.shape)


def moe_ff(ops, t: dict, p: dict, h, held=None):
    """``(routed part + shared expert [T, D], counts, swapped)``."""
    w, e, counts, swapped = route(t, h, p["router"])
    shared = swiglu(ops, h, p["shared_gate"]["kernel"],
                    p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    return experts(ops, t, p, h, w, e, held) + shared, counts, swapped


def layer(ops, t: dict, p: dict, x, layer_type: str, dense: bool,
          control=None):
    """One layer on one sequence ``x [T, D]``: ``(x, (counts, swapped))``,
    ``()`` of a dense layer."""
    eps = t["rms_norm_eps"]
    norm = lambda a, name: rt.rms(a, p[name]["scale"], eps)  # noqa: E731
    x = x + norm(attention_op(ops, t, p, norm(x, "attn_norm"), layer_type,
                              control), "op_post_norm")
    if dense:
        out = swiglu(ops, norm(x, "mlp_norm"), p["w1"]["kernel"],
                     p["w3"]["kernel"], p["w2"]["kernel"])
        return x + norm(out, "ff_post_norm"), ()
    out, counts, swapped = moe_ff(ops, t, p, norm(x, "moe_norm"))
    return x + norm(out, "ff_post_norm"), (counts, swapped)


def torso(ops, t: dict, params: dict, obs, control=None):
    """``obs [B, tokens] -> (latent [B, D], counts [expert layers, experts],
    swapped [expert layers])``."""
    x = t["embedding_multiplier"] * params["embed"]["kernel"][
        rt.tokenise(t, obs)]
    counts, swapped = [], []
    for i, layer_type in enumerate(t["layer_types"]):
        dense = i < t["num_dense_layers"]
        one = jax.checkpoint(lambda p, xs, lt=layer_type, dense=dense: layer(
            ops, t, p, xs, lt, dense, control))
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
    rows = range(t["num_dense_layers"], len(t["layer_types"]))
    for row, i in enumerate(rows):
        n = counts[row].astype(jnp.float32)
        lay = layers[f"layer_{i}"]
        bias = lay["router"]["bias"] + t["bias_update_rate"] * jnp.sign(
            jnp.mean(n) - n)
        layers[f"layer_{i}"] = {**lay, "router": {**lay["router"],
                                                  "bias": bias}}
    return {**critic, "params": {**critic["params"], "torso": layers}}


def _parts(cfg: dict, ops, control):
    t = cfg["torso"]
    head = lambda p, z, a: reference.critic_mlp(  # noqa: E731
        ops, p["params"]["critic"], z, a)
    latent = lambda p, x: torso(  # noqa: E731
        ops, t, p["params"]["torso"], x, control)
    pi = lambda p, z: reference.actor_mlp(ops, p["params"], z)  # noqa: E731
    return head, latent, pi


def target(cfg: dict, ops, st: dict, batch, control=None):
    """The first pass: the target networks' distribution of the next row,
    projected onto the support."""
    head, latent, pi = _parts(cfg, ops, control)
    _obs, _action, reward, next_obs, discount = batch
    z_next = latent(st["t_critic"], next_obs)[0]
    t_probs = head(st["t_critic"], z_next, pi(st["t_actor"], z_next))
    return jax.lax.stop_gradient(
        reference.project(cfg, t_probs, reward, discount))


def critic_grads(cfg: dict, ops, critic: dict, batch, w, proj, control=None):
    """The second pass, differentiated: ``(gradients, metrics)``."""
    head, latent, _pi = _parts(cfg, ops, control)
    obs, action = batch[:2]

    def critic_loss(p):
        z, counts, swapped = latent(p, obs)
        td = -jnp.sum(proj * jnp.log(head(p, z, action) + LOG_EPS), axis=-1)
        return jnp.mean(w * td), (td, counts, swapped)

    (c_loss, (td, counts, swapped)), grads = jax.value_and_grad(
        critic_loss, has_aux=True)(critic)
    return grads, {"critic_loss": c_loss, "td_error": td,
                   "route_counts": counts, "bias_swapped": swapped}


def critic_adam(cfg: dict, st: dict, grads: dict, counts) -> dict:
    """The critic's Adam step on the state, then the bias rule."""
    critic, cm, cv, count = reference.adam(
        st["critic"], grads, st["cm"], st["cv"], st["count"],
        cfg["lr_critic"])
    return {**st, "critic": balance(cfg["torso"], critic, counts), "cm": cm,
            "cv": cv, "count": count}


def actor_update(cfg: dict, ops, st: dict, count, batch, control=None):
    """The third pass through the stepped critic, the actor's Adam step
    (``count`` the step count before this step) and both target averages:
    ``(state, actor loss)``."""
    head, latent, pi = _parts(cfg, ops, control)
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
           control=None):
    """``reference_torso.follow`` for this step: ``n_steps`` from the state
    ``st`` (``init``), which is given up. Returns per-step metrics (host
    numpy) and the final state. ``key`` is the program's; the step draws
    nothing from it.

    The step's passes are programs of their own, and while the gradient is
    taken the target torso and both Adam moments (``PARKED``, 5.4 GB at the
    cell's size) wait on the host: the differentiated pass of two
    16,384-token sequences then has the chip to itself beside the critic."""
    del key
    assert control in CONTROLS, control
    cfg = reference.model_cfg(cfg_model)
    with jax.default_matmul_precision("highest"):
        first = jax.jit(lambda st, batch: target(cfg, ops, st, batch,
                                                 control))
        second = jax.jit(lambda critic, batch, w, proj: critic_grads(
            cfg, ops, critic, batch, w, proj, control))
        adam = jax.jit(lambda st, grads, counts: critic_adam(
            cfg, st, grads, counts), donate_argnums=(0,))
        third = jax.jit(lambda st, count, batch: actor_update(
            cfg, ops, st, count, batch, control), donate_argnums=(0,))
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
