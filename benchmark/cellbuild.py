"""Build a learner cell from the program's own classes, as ``train.train()``
wires them: ``D4PGConfig`` -> state, ``FusedDeviceReplay``, ``FusedLoop``
(and ``ReplayService`` in the ingest driver). Data and weights come from
``datagen`` and the seed; nothing here is timed except as set-up.
"""

from __future__ import annotations

import copy
import json
import os

import numpy as np

from benchmark import datagen

ROOT = os.path.dirname(os.path.abspath(__file__))


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if (
            isinstance(v, dict) and isinstance(out.get(k), dict)) else v
    return out


def load_json(kind: str, name: str) -> dict:
    """``benchmark/<kind>/<name>.json`` — the only way a cell's files are
    found: by the names in ``BENCHMARK.json``."""
    with open(os.path.join(ROOT, kind, name + ".json")) as f:
        return json.load(f)


def load_config(name: str, rehearsal: bool) -> dict:
    cfg = load_json("configs", name)
    if rehearsal:
        cfg = _merge(cfg, cfg["rehearsal"])
    return cfg


def load_traffic(name: str, rehearsal: bool) -> dict:
    traffic = load_json("traffic", name)
    if rehearsal:
        traffic = _merge(traffic, traffic.get("rehearsal", {}))
    return traffic


def learner_config(cfg: dict):
    from d4pg_tpu.learner import D4PGConfig

    m = dict(cfg["model"])
    for key in ("hidden", "obs_shape", "encoder_channels"):
        if key in m:
            m[key] = tuple(m[key])
    return D4PGConfig(**m)


def row_spec(cfg: dict, config) -> dict:
    return {
        "obs_shape": (tuple(config.obs_shape) if config.pixels
                      else (config.obs_dim,)),
        "act_dim": config.act_dim,
        "discount": cfg["data"]["discount"],
    }


def param_templates(config):
    """Shapes of the actor and critic parameter trees, from the program's
    ``init_state`` without running it."""
    import jax
    from d4pg_tpu.learner import init_state

    shapes = jax.eval_shape(lambda: init_state(config, jax.random.key(0)))
    return shapes.actor_params, shapes.critic_params


def seeded_params(config, seed32):
    """(actor, critic) parameter trees from the seed; with a shared encoder
    the actor's encoder subtree holds the critic's values, as
    ``init_state`` ties them."""
    import jax.numpy as jnp

    actor_t, critic_t = param_templates(config)
    actor = datagen.weights(jnp, seed32, actor_t)
    critic = datagen.weights(jnp, seed32 ^ jnp.uint32(0x5BD1E995), critic_t)
    if config.share_encoder:
        actor = {**actor, "params": {
            **actor["params"], "encoder": critic["params"]["encoder"]}}
    return actor, critic


def build_state(config, seed32):
    """The learner state in one jitted call from the seed: weights in
    float32 (the type the learner keeps them in), targets as copies, Adam
    states zero, the step's PRNG key from the seed."""
    import jax
    import jax.numpy as jnp
    from d4pg_tpu.learner import D4PGState

    def make(s):
        actor, critic = seeded_params(config, s)
        dup = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
        actor = dup(actor)  # the tied encoder must not alias the critic's
        return D4PGState(
            actor_params=actor, critic_params=critic,
            target_actor_params=dup(actor), target_critic_params=dup(critic),
            actor_opt_state=config.optimizer(config.lr_actor).init(actor),
            critic_opt_state=config.optimizer(config.lr_critic).init(critic),
            key=jax.random.key(s), step=jnp.zeros((), jnp.int32))

    return jax.jit(make)(jnp.uint32(seed32))


def seeded_p_alpha(cfg: dict, seed32):
    """Leaf priorities ``p ** alpha`` of the seeded fill, one per ring row."""
    import jax
    import jax.numpy as jnp

    capacity = int(cfg["replay"]["capacity"])
    return jax.jit(lambda s: datagen.priorities(
        jnp, s, jnp.arange(capacity), cfg["data"]["priority_decades"])
        ** cfg["learner"]["per_alpha"])(jnp.uint32(seed32))


def _fill_block_rows(capacity: int, row_bytes: int) -> int:
    """Largest divisor of ``capacity`` whose block stays under 128 MB, so
    the fill's temporaries never set the memory peak."""
    limit = max(1, min(8192, (128 << 20) // max(1, row_bytes)))
    return max(d for d in range(1, limit + 1) if capacity % d == 0)


def build_buffer(cfg: dict, config, seed32):
    """A ``FusedDeviceReplay`` filled to capacity on the device from the
    seed. Rows are written in place block by block into the donated ring
    (a second whole ring would double the memory peak), then the handles,
    ``size``/``head`` and the seeded leaf priorities are handed over the
    way a checkpoint restore does. ``_store.swap_arrays`` is the program's
    one non-public name used here: it has no public device-side fill
    (PERF.md, Open questions)."""
    import jax
    import jax.numpy as jnp
    from d4pg_tpu.replay import device_per as dper
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay
    from d4pg_tpu.replay.uniform import TransitionBatch

    rp, lr = cfg["replay"], cfg["learner"]
    capacity = int(rp["capacity"])
    spec = row_spec(cfg, config)
    buffer = FusedDeviceReplay(
        capacity, config.obs_spec, config.act_dim, alpha=lr["per_alpha"],
        prioritized=True, block_rows=int(rp["block_rows"]),
        staging_blocks=int(rp.get("staging_blocks", 8)), ingest_shards=1)
    row_bytes = sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                    for a in buffer.storage)
    block = _fill_block_rows(capacity, row_bytes)

    def fill(storage, s):
        def body(b, storage):
            start = b * block
            new = datagen.rows(jnp, s, start + jnp.arange(block), spec)
            return TransitionBatch(*[
                jax.lax.dynamic_update_slice_in_dim(arr, val, start, 0)
                for arr, val in zip(storage, new)])
        return jax.lax.fori_loop(0, capacity // block, body, storage)

    s = jnp.uint32(seed32)
    storage = jax.jit(fill, donate_argnums=(0,))(buffer.storage, s)
    buffer._store.swap_arrays(storage)
    buffer.size, buffer.head = capacity, 0
    buffer.trees = dper.set_leaves_jitted(
        buffer.trees, jnp.arange(capacity), seeded_p_alpha(cfg, seed32))
    return buffer


def build_loop(cfg: dict, config, buffer, service=None):
    from d4pg_tpu.learner.loop import FusedLoop

    lr = cfg["learner"]
    return FusedLoop(
        config, buffer, k=int(lr["k"]), batch_size=int(lr["batch_size"]),
        prioritized=True, alpha=lr["per_alpha"], beta0=lr["per_beta0"],
        beta_steps=int(lr["per_beta_steps"]), service=service, donate=True)
