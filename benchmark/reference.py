"""The plain reference: one D4PG gradient step in straightforward float32
``jax.numpy``. No kernels, no scan, no replay machinery, nothing imported
from the program. It follows the published description (Barth-Maron et al.
2018: categorical critic, Bellman projection onto the support,
cross-entropy critic loss with importance weights, expected-Q actor loss,
Adam, Polyak targets) with the program's documented choices: the actor loss
uses the critic just stepped, a shared pixel encoder is trained by the
critic loss alone, and DrQ's random shift draws independent offsets for
``obs`` and ``next_obs``.

``dot``/``conv`` are injectable so that the same mathematics can be run in
the next lower precision as the control (``lowp_ops``): the configuration
states bfloat16 matmuls, the control rounds every matmul input to fp8
(e4m3, per-tensor scale) in the forward pass and leaves the backward pass
exact, which is the mildest way a later PR could be tempted to cut.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
PRIORITY_EPS = 1e-6
LOG_EPS = 1e-10


# -- matmul / conv in a chosen precision ------------------------------------
def _dot(x, w):
    return jnp.dot(x, w, precision=HI)


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI)


EXACT_OPS = {"dot": _dot, "conv": _conv}


def _fp8(x):
    """Round to float8_e4m3 at a per-tensor scale; identity gradient."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


LOWP_OPS = {
    "dot": lambda x, w: _dot(_fp8(x), _fp8(w)),
    "conv": lambda x, w, s: _conv(_fp8(x), _fp8(w), s),
}


# -- networks ---------------------------------------------------------------
def _dense(ops, p, x):
    return ops["dot"](x, p["kernel"]) + p["bias"]


def _mlp_names(p, prefix="fc"):
    return sorted((k for k in p if k.startswith(prefix)),
                  key=lambda k: int(k[len(prefix):]))


def actor_mlp(ops, p, x):
    for name in _mlp_names(p):
        x = jax.nn.relu(_dense(ops, p[name], x))
    return jnp.tanh(_dense(ops, p["out"], x))


def critic_mlp(ops, p, x, action):
    names = _mlp_names(p["torso"])
    x = jax.nn.relu(_dense(ops, p["torso"][names[0]], x))
    x = jnp.concatenate([x, action], axis=-1)
    for name in names[1:]:
        x = jax.nn.relu(_dense(ops, p["torso"][name], x))
    return jax.nn.softmax(_dense(ops, p["head"], x), axis=-1)


def encoder(ops, p, pixels):
    x = pixels.astype(jnp.float32) / 255.0
    for i, name in enumerate(_mlp_names(p, "conv")):
        x = ops["conv"](x, p[name]["kernel"], 2 if i == 0 else 1)
        x = jax.nn.relu(x + p[name]["bias"])
    x = x.reshape(x.shape[0], -1)
    x = _dense(ops, p["proj"], x)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + 1e-6)
    return jnp.tanh(x * p["ln"]["scale"] + p["ln"]["bias"])


def actor_apply(ops, cfg, params, obs, detach=False):
    p = params["params"]
    if not cfg["pixels"]:
        return actor_mlp(ops, p, obs)
    z = encoder(ops, p["encoder"], obs)
    if detach:
        z = jax.lax.stop_gradient(z)
    return actor_mlp(ops, p["actor"], z)


def critic_apply(ops, cfg, params, obs, action):
    p = params["params"]
    if not cfg["pixels"]:
        return critic_mlp(ops, p, obs, action)
    return critic_mlp(ops, p["critic"], encoder(ops, p["encoder"], obs),
                      action)


# -- the step's parts -------------------------------------------------------
def atoms(cfg):
    return jnp.linspace(cfg["v_min"], cfg["v_max"], cfg["n_atoms"])


def project(cfg, probs, reward, discount):
    """Bellman projection by its per-atom definition: source atom ``i``
    lands at ``b = (clip(r + d z_i) - v_min) / delta`` and splits its mass
    between ``floor(b)`` and ``ceil(b)`` in proportion to closeness; an
    integral ``b`` keeps all of it."""
    n = cfg["n_atoms"]
    delta = (cfg["v_max"] - cfg["v_min"]) / (n - 1)
    tz = jnp.clip(reward[:, None] + discount[:, None] * atoms(cfg)[None, :],
                  cfg["v_min"], cfg["v_max"])
    b = (tz - cfg["v_min"]) / delta
    lo, up = jnp.floor(b), jnp.ceil(b)
    same = (lo == up).astype(jnp.float32)
    out = []
    for j in range(n):
        to_lo = probs * ((up - b) + same) * (lo == j)
        to_up = probs * (b - lo) * (up == j)
        out.append(jnp.sum(to_lo + to_up, axis=-1))
    return jnp.stack(out, axis=-1)


def shift(key, imgs, pad):
    """DrQ random shift: edge-pad by ``pad``, crop back at an offset drawn
    per sample from ``fold_in(key, i)`` uniformly on ``[0, 2 pad]^2``."""
    b, h, w, c = imgs.shape
    padded = jnp.pad(imgs, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     mode="edge")
    offs = jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(key, i), (2,), 0, 2 * pad + 1))(jnp.arange(b))
    return jax.vmap(lambda img, o: jax.lax.dynamic_slice(
        img, (o[0], o[1], 0), (h, w, c)))(padded, offs)


def adam(params, grads, m, v, count, lr):
    count = count + 1
    m = jax.tree_util.tree_map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                               m, grads)
    v = jax.tree_util.tree_map(
        lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, v, grads)
    c1 = 1 - ADAM_B1 ** count
    c2 = 1 - ADAM_B2 ** count
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, m, v)
    return params, m, v, count


def _tie(actor, critic):
    return {**actor, "params": {**actor["params"],
                                "encoder": critic["params"]["encoder"]}}


def init(actor, critic):
    """Reference state from seeded parameters: targets equal the online
    networks, Adam moments are zero."""
    zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)  # noqa: E731
    return {
        "actor": actor, "critic": critic, "t_actor": actor,
        "t_critic": critic, "am": zeros(actor), "av": zeros(actor),
        "cm": zeros(critic), "cv": zeros(critic),
        "count": jnp.zeros((), jnp.float32),
    }


def step(cfg, ops, st, batch, w, key):
    """One gradient step. ``batch`` = (obs, action, reward, next_obs,
    discount); ``w`` the importance weights; ``key`` the learner's PRNG key
    *before* the step (only the pixel shift draws from it). Returns the new
    state, the metrics the program reports and the key after the step."""
    obs, action, reward, next_obs, discount = batch
    # the fused chunk splits off a sampling key, then the update splits
    # again; the shift keys come from the second half of that split
    _k_sample, key = jax.random.split(key)
    key, sub = jax.random.split(key)
    if cfg["augment"] == "shift":
        _sub, k_obs, k_next = jax.random.split(sub, 3)
        obs = shift(k_obs, obs, cfg["augment_pad"])
        next_obs = shift(k_next, next_obs, cfg["augment_pad"])

    next_a = actor_apply(ops, cfg, st["t_actor"], next_obs)
    t_probs = critic_apply(ops, cfg, st["t_critic"], next_obs, next_a)
    proj = jax.lax.stop_gradient(project(cfg, t_probs, reward, discount))

    def critic_loss(p):
        probs = critic_apply(ops, cfg, p, obs, action)
        td = -jnp.sum(proj * jnp.log(probs + LOG_EPS), axis=-1)
        return jnp.mean(w * td), td

    (c_loss, td), c_grads = jax.value_and_grad(critic_loss, has_aux=True)(
        st["critic"])
    critic, cm, cv, count = adam(st["critic"], c_grads, st["cm"], st["cv"],
                                 st["count"], cfg["lr_critic"])
    shared = cfg["pixels"] and cfg["share_encoder"]
    actor_in = _tie(st["actor"], critic) if shared else st["actor"]

    def actor_loss(p):
        a = actor_apply(ops, cfg, p, obs, detach=shared)
        probs = critic_apply(ops, cfg, critic, obs, a)
        return -jnp.mean(jnp.sum(probs * atoms(cfg), axis=-1))

    a_loss, a_grads = jax.value_and_grad(actor_loss)(actor_in)
    actor, am, av, _ = adam(actor_in, a_grads, st["am"], st["av"],
                            st["count"], cfg["lr_actor"])
    if shared:
        actor = _tie(actor, critic)
    tau = cfg["tau"]
    soft = lambda t, o: jax.tree_util.tree_map(  # noqa: E731
        lambda t, o: (1 - tau) * t + tau * o, t, o)
    t_actor, t_critic = soft(st["t_actor"], actor), soft(st["t_critic"],
                                                         critic)
    if shared:
        t_actor = _tie(t_actor, t_critic)
    new = {"actor": actor, "critic": critic, "t_actor": t_actor,
           "t_critic": t_critic, "am": am, "av": av, "cm": cm, "cv": cv,
           "count": count}
    metrics = {"critic_loss": c_loss, "actor_loss": a_loss, "td_error": td}
    return new, metrics, key


def model_cfg(model: dict) -> dict:
    """The reference's view of a configuration file's ``model`` block, with
    the defaults the source papers and the program share written out."""
    out = {"pixels": False, "share_encoder": False, "augment": "none",
           "augment_pad": 4, "tau": 0.001, "lr_actor": 1e-4,
           "lr_critic": 1e-3}
    out.update(model)
    return out


class PriorityMirror:
    """The priorities as a plain array: importance weights from it, TD
    write-back into it. float64 on the host; the program keeps float32 sum
    and min trees."""

    def __init__(self, p_alpha: np.ndarray, alpha: float, beta0: float,
                 beta_steps: int):
        self.leaf = np.asarray(p_alpha, np.float64).copy()
        self.alpha, self.beta0, self.beta_steps = alpha, beta0, beta_steps

    def is_weights(self, idx: np.ndarray, step: int) -> np.ndarray:
        frac = min(max(step / float(self.beta_steps), 0.0), 1.0)
        beta = self.beta0 + frac * (1.0 - self.beta0)
        n, total = self.leaf.shape[0], self.leaf.sum()
        max_w = (self.leaf.min() / total * n) ** (-beta)
        return ((self.leaf[idx] / total * n) ** (-beta) / max_w).astype(
            np.float32)

    def write_back(self, idx: np.ndarray, td: np.ndarray) -> None:
        self.leaf[idx] = (np.abs(np.asarray(td, np.float64))
                          + PRIORITY_EPS) ** self.alpha


def follow(cfg_model: dict, ops, actor, critic, key, feed, mirror,
           n_steps: int):
    """Follow ``n_steps`` gradient steps from seeded parameters. ``feed(t)``
    gives step ``t``'s rows ``(idx, batch)`` — the rows the program drew.
    Returns per-step metrics (host numpy) and the final state."""
    cfg = model_cfg(cfg_model)
    jstep = jax.jit(lambda st, batch, w, key: step(cfg, ops, st, batch, w,
                                                   key))
    st = init(actor, critic)
    out = {"critic_loss": [], "actor_loss": [], "td_error": []}
    for t in range(n_steps):
        idx, batch = feed(t)
        w = jnp.asarray(mirror.is_weights(idx, t))
        st, metrics, key = jstep(st, batch, w, key)
        td = np.asarray(metrics["td_error"])
        mirror.write_back(idx, td)
        out["critic_loss"].append(float(metrics["critic_loss"]))
        out["actor_loss"].append(float(metrics["actor_loss"]))
        out["td_error"].append(td)
    return {k: np.asarray(v) for k, v in out.items()}, st


def leaf_norms(tree) -> np.ndarray:
    return np.asarray([math.sqrt(float(jnp.sum(jnp.square(
        x.astype(jnp.float32))))) for x in jax.tree_util.tree_leaves(tree)])
