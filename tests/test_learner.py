"""Learner-layer tests: the jit'd D4PG update (SURVEY.md §4 test strategy).

Covers: state init/target equality, one-step mechanics (step counter, target
soft-update direction), loss decrease on a synthetic fixed-point task,
determinism (same seed => bitwise-identical params — the property that
replaces the reference's hogwild races by construction, SURVEY.md §5), PER
weight plumbing, and the MoG critic family end-to-end.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.learner import D4PGConfig, act, act_deterministic, init_state, make_update
from d4pg_tpu.replay.uniform import TransitionBatch

OBS, ACT, B = 3, 1, 32


def _config(**kw):
    base = dict(obs_dim=OBS, act_dim=ACT, v_min=-10.0, v_max=10.0, n_atoms=11,
                hidden=(32, 32, 32))
    base.update(kw)
    return D4PGConfig(**base)


def _batch(rng, done_frac=0.25, gamma=0.99):
    done = (rng.random(B) < done_frac).astype(np.float32)
    return TransitionBatch(
        obs=rng.standard_normal((B, OBS)).astype(np.float32),
        action=rng.uniform(-1, 1, (B, ACT)).astype(np.float32),
        reward=rng.standard_normal(B).astype(np.float32),
        next_obs=rng.standard_normal((B, OBS)).astype(np.float32),
        done=done,
        discount=(gamma * (1.0 - done)).astype(np.float32),
    )


def test_init_targets_equal_online():
    config = _config()
    state = init_state(config, jax.random.key(0))
    chex = jax.tree_util.tree_all(
        jax.tree_util.tree_map(
            lambda a, b: jnp.array_equal(a, b),
            state.actor_params,
            state.target_actor_params,
        )
    )
    assert chex
    assert int(state.step) == 0


def test_update_step_mechanics(rng):
    config = _config()
    state = init_state(config, jax.random.key(0))
    update = make_update(config, donate=False)
    batch = _batch(rng)
    w = jnp.ones((B,), jnp.float32)
    new_state, metrics = update(state, batch, w)
    assert int(new_state.step) == 1
    assert metrics["td_error"].shape == (B,)
    assert np.isfinite(float(metrics["critic_loss"]))
    # targets moved strictly toward online params, by a tau-sized amount
    def moved(t_old, t_new, online):
        d_old = jnp.abs(t_old - online).sum()
        d_new = jnp.abs(t_new - online).sum()
        return float(d_new) <= float(d_old) + 1e-6

    flat_old = jax.tree_util.tree_leaves(state.target_critic_params)
    flat_new = jax.tree_util.tree_leaves(new_state.target_critic_params)
    flat_onl = jax.tree_util.tree_leaves(new_state.critic_params)
    assert all(moved(a, b, c) for a, b, c in zip(flat_old, flat_new, flat_onl))


def test_loss_decreases_on_fixed_task(rng):
    """On a fixed batch, repeated updates must reduce the critic loss."""
    config = _config(lr_actor=1e-3, lr_critic=1e-3)
    state = init_state(config, jax.random.key(1))
    update = make_update(config, donate=False)
    batch = _batch(rng)
    first = None
    for i in range(60):
        state, metrics = update(state, batch, None)
        if first is None:
            first = float(metrics["critic_loss"])
    assert float(metrics["critic_loss"]) < first


def test_determinism_same_seed(rng):
    """Same seed + same data => bitwise-identical parameters (SURVEY.md §5:
    the synchronous design removes the reference's races by construction)."""
    config = _config()
    batch = _batch(rng)
    outs = []
    for _ in range(2):
        state = init_state(config, jax.random.key(7))
        update = make_update(config, donate=False)
        for _ in range(3):
            state, _ = update(state, batch, None)
        outs.append(state)
    for a, b in zip(
        jax.tree_util.tree_leaves(outs[0].actor_params),
        jax.tree_util.tree_leaves(outs[1].actor_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_is_weights_scale_loss(rng):
    """Zero IS weights must zero the critic gradient; uniform weights match
    the unweighted loss."""
    config = _config()
    state = init_state(config, jax.random.key(2))
    update = make_update(config, donate=False)
    batch = _batch(rng)
    _, m_uniform = update(state, batch, jnp.ones((B,), jnp.float32))
    s_zero, m_zero = update(state, batch, jnp.zeros((B,), jnp.float32))
    assert float(m_zero["critic_loss"]) == 0.0
    # with zero weights the critic params must not move
    for a, b in zip(
        jax.tree_util.tree_leaves(state.critic_params),
        jax.tree_util.tree_leaves(s_zero.critic_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    assert float(m_uniform["critic_loss"]) > 0.0


def test_mog_family_end_to_end(rng):
    """The reference's empty mixture_of_gaussian stub (models.py:63-65,
    85-87), implemented for real: full update runs and improves."""
    config = _config(critic_family="mog", n_components=3, mog_samples=16)
    state = init_state(config, jax.random.key(3))
    update = make_update(config, donate=False)
    batch = _batch(rng)
    first = None
    for _ in range(40):
        state, metrics = update(state, batch, None)
        if first is None:
            first = float(metrics["critic_loss"])
    assert np.isfinite(float(metrics["critic_loss"]))
    assert metrics["td_error"].shape == (B,)


def test_multi_update_equals_sequential(rng):
    """make_multi_update (lax.scan K-per-dispatch) must match K sequential
    update_step calls bitwise — same PRNG chain, same Adam math."""
    from d4pg_tpu.learner import make_multi_update

    config = _config()
    K = 3
    batches = [_batch(np.random.default_rng(i)) for i in range(K)]
    w = np.ones((K, B), np.float32)

    seq_state = init_state(config, jax.random.key(11))
    seq_update = make_update(config, donate=False)
    for i in range(K):
        seq_state, seq_m = seq_update(seq_state, batches[i], jnp.asarray(w[i]))

    stacked = TransitionBatch(*[np.stack(x) for x in zip(*batches)])
    multi_state = init_state(config, jax.random.key(11))
    multi = make_multi_update(config, donate=False)
    multi_state, multi_m = multi(multi_state, stacked, jnp.asarray(w))

    assert int(multi_state.step) == K
    np.testing.assert_array_equal(
        np.asarray(multi_m["td_error"][-1]), np.asarray(seq_m["td_error"]))
    for a, b in zip(jax.tree_util.tree_leaves(seq_state.critic_params),
                    jax.tree_util.tree_leaves(multi_state.critic_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_update_loop_is_steady_state(rng):
    """The learner hot path must hit the jit cache after warmup: repeated
    update calls (fresh batch values, same shapes/dtypes) may not trigger a
    single XLA compilation. Guards the invariant every measured rate
    depends on — a weak-type or shape instability here would silently turn
    throughput numbers into compile-time measurements."""
    from d4pg_tpu.io.profiling import RecompileSentinel

    config = _config()
    state = init_state(config, jax.random.key(0))
    update = make_update(config, donate=False)
    state, _ = update(state, _batch(rng), jnp.ones((B,), jnp.float32))  # warmup
    with RecompileSentinel() as sentinel:
        for i in range(3):
            batch = _batch(np.random.default_rng(i))
            state, metrics = update(state, batch, jnp.ones((B,), jnp.float32))
    jax.block_until_ready(metrics["critic_loss"])
    sentinel.assert_clean("learner update loop")


def test_act_shapes_and_bounds(rng):
    config = _config()
    state = init_state(config, jax.random.key(4))
    obs = jnp.asarray(rng.standard_normal((B, OBS)), jnp.float32)
    a = act(config, state.actor_params, obs, jax.random.key(5), epsilon=0.3)
    assert a.shape == (B, ACT)
    assert float(jnp.max(jnp.abs(a))) <= 1.0
    g = act_deterministic(config, state.actor_params, obs)
    assert float(jnp.max(jnp.abs(g))) <= 1.0
    # exploratory differs from greedy
    assert float(jnp.max(jnp.abs(a - g))) > 0.0


def test_bfloat16_compute_dtype(rng):
    """bf16 matmuls (MXU-native): update runs, losses stay float32-finite,
    and the critic still improves on a fixed task."""
    config = _config(compute_dtype="bfloat16")
    state = init_state(config, jax.random.key(6))
    update = make_update(config, donate=False)
    batch = _batch(rng)
    first = None
    for _ in range(40):
        state, metrics = update(state, batch, None)
        if first is None:
            first = float(metrics["critic_loss"])
    assert metrics["critic_loss"].dtype == jnp.float32
    assert float(metrics["critic_loss"]) < first
    # params stay float32 (bf16 is compute-only)
    leaf = jax.tree_util.tree_leaves(state.critic_params)[0]
    assert leaf.dtype == jnp.float32


def test_bad_compute_dtype_rejected():
    with pytest.raises(ValueError):
        _config(compute_dtype="float16")


def test_action_l2_penalty(rng):
    """action_l2 adds exactly l2 * mean(|pi(s)|^2) to the actor loss (the
    HER recipe's penalty; 0 = reference objective) and flows into training."""
    from d4pg_tpu.learner.update import _actor_loss_fn

    base_cfg = _config()
    pen_cfg = _config(action_l2=0.5)
    state = init_state(base_cfg, jax.random.key(0))
    batch = _batch(rng)
    actor = base_cfg.build_actor()
    a = actor.apply(state.actor_params, batch.obs)
    expected_pen = 0.5 * float(jnp.mean(jnp.square(a)))  # baselines norm
    base = float(_actor_loss_fn(base_cfg, state.actor_params,
                                state.critic_params, batch))
    pen = float(_actor_loss_fn(pen_cfg, state.actor_params,
                               state.critic_params, batch))
    np.testing.assert_allclose(pen - base, expected_pen, rtol=1e-5)
    # and the jit'd update accepts the config (static field, new cache key)
    update = make_update(pen_cfg, donate=False)
    new_state, metrics = update(state, batch, jnp.ones((B,), jnp.float32))
    assert np.isfinite(float(metrics["actor_loss"]))


@pytest.mark.parametrize("projection",
                         ["scatter", "pallas", "pallas_ce", "auto"])
def test_bad_projection_rejected(projection):
    """``projection`` has one value: the mesh factories and the update can
    no longer be handed anything but the einsum."""
    with pytest.raises(ValueError, match="projection"):
        _config(projection=projection)


# --- the one step's tail, for every family of model (PR 44) ----------------

TORSO_FILES = {"mellum2": "test_torso", "keye2": "test_torso_sparse",
               "lfm2": "test_torso_hybrid", "qwen3next": "test_torso_linear",
               "ouro": "test_torso_loop"}


def _case(name, rng):
    """``(config, batch)`` of a small model of each family: a plain MLP, the
    mixture-of-Gaussians critic, pixels with the shared encoder and the DrQ
    shift, and the small torso of each name from its own test file."""
    if name in TORSO_FILES:
        import importlib

        mod = importlib.import_module(TORSO_FILES[name])
        assert mod.MODEL["torso"]["name"] == name
        return D4PGConfig(**mod.MODEL), mod.small_batch()
    if name == "pixels":
        shape = (16, 16, 3)
        config = D4PGConfig(
            obs_dim=int(np.prod(shape)), act_dim=2, v_min=-20.0, v_max=0.0,
            n_atoms=11, hidden=(32, 32), pixels=True, obs_shape=shape,
            share_encoder=True, augment="shift", augment_pad=2)
        n = 8
        return config, TransitionBatch(
            obs=rng.integers(0, 255, (n, *shape), dtype=np.uint8),
            action=rng.uniform(-1, 1, (n, 2)).astype(np.float32),
            reward=rng.standard_normal(n).astype(np.float32),
            next_obs=rng.integers(0, 255, (n, *shape), dtype=np.uint8),
            done=np.zeros(n, np.float32),
            discount=np.full(n, 0.99, np.float32))
    if name == "mog":
        return _config(critic_family="mog", n_components=3,
                       mog_samples=8), _batch(rng)
    return _config(), _batch(rng)


def _leaves(tree):
    """Leaves by path, as numpy (a PRNG key as its data)."""
    return {jax.tree_util.keystr(path): np.asarray(
        jax.random.key_data(x) if jnp.issubdtype(x.dtype, jax.dtypes.prng_key)
        else x) for path, x in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize(
    "name", ["mlp", "mog", "pixels", *sorted(TORSO_FILES)])
def test_the_steps_tail_is_the_same_for_every_family(name, rng):
    """What ``update_step`` does after the two losses, whatever reads the
    networks: the step counts up, the key is the first half of the split,
    both targets are ``soft_update(old target, new online, tau)`` bitwise
    (the target actor's encoder tied after it where it is shared), and a
    parameter that neither loss reaches and no optimizer moves stays as it
    was, but for a router's balancing bias, which moves."""
    from d4pg_tpu.core.updates import soft_update, tie_encoder
    from d4pg_tpu.learner import update as update_lib

    config, batch = _case(name, rng)
    state = init_state(config, jax.random.key(3))
    w = jnp.linspace(0.5, 1.0, batch.reward.shape[0])
    new, metrics = make_update(config, donate=False)(state, batch, w)

    assert int(new.step) == int(state.step) + 1
    key, _sub = jax.random.split(state.key)
    np.testing.assert_array_equal(jax.random.key_data(new.key),
                                  jax.random.key_data(key))
    assert set(metrics) >= {"critic_loss", "actor_loss", "q_mean",
                            "td_error"}
    assert float(metrics["q_mean"]) == -float(metrics["actor_loss"])

    soft = jax.jit(lambda t, o: soft_update(t, o, config.tau))
    want_critic = soft(state.target_critic_params, new.critic_params)
    want_actor = soft(state.target_actor_params, new.actor_params)
    if config.share_encoder:
        want_actor = tie_encoder(want_actor, want_critic)
    for got, want in ((new.target_critic_params, want_critic),
                      (new.target_actor_params, want_actor)):
        got, want = _leaves(got), _leaves(want)
        assert got.keys() == want.keys()
        for path in got:
            np.testing.assert_array_equal(got[path], want[path], path)

    # the critic's leaves the critic loss does not reach: from zero Adam
    # moments the optimizer leaves them where they were
    family = update_lib._PLAIN if config.torso is None else update_lib._TORSO
    key, sub = jax.random.split(state.key)
    hooked, sub = family.batch_hook(config, batch, sub)
    loss_fn = family.critic_loss(config, state, hooked, w, sub)
    grads = _leaves(jax.jit(jax.grad(lambda p: loss_fn(p)[0]))(
        state.critic_params))
    old, stepped = _leaves(state.critic_params), _leaves(new.critic_params)
    unreached = [p for p, g in grads.items() if not g.any()]
    moved = [p for p in unreached if not np.array_equal(old[p], stepped[p])]
    biased = config.torso is not None and config.torso.use_expert_bias
    assert all("router" in p and p.endswith("['bias']") for p in moved), moved
    assert bool(moved) == biased, (moved, unreached)
    # and what the loss reaches, the optimizer moved
    reached = [p for p in grads if p not in unreached]
    assert reached and all(
        not np.array_equal(old[p], stepped[p]) for p in reached)


def _uniform_program(builder, config, batch):
    """``(the builder's jitted function, its operands with and without
    weights or trees, the pure function jitted by hand)``."""
    from d4pg_tpu.learner import make_multi_update
    from d4pg_tpu.learner.fused import (device_replay, fused_chunk_step,
                                        make_fused_chunk)
    from d4pg_tpu.learner.update import multi_update_step, update_step
    from d4pg_tpu.replay import device_per as dper

    n = batch.reward.shape[0]
    if builder == "update":
        return (make_update(config, donate=False),
                (batch, jnp.ones((n,), jnp.float32)), (batch, None),
                jax.jit(lambda s, b: update_step(config, s, b, None)))
    if builder == "multi_update":
        stacked = jax.tree_util.tree_map(lambda x: np.stack([x, x[::-1]]),
                                         batch)
        return (make_multi_update(config, donate=False),
                (stacked, jnp.ones((2, n), jnp.float32)), (stacked, None),
                jax.jit(lambda s, b: multi_update_step(config, s, b, None)))
    trees = dper.insert(dper.init(n), jnp.arange(n), 0.6)
    sample, write_back = device_replay(8, 0.6, 0.4, 100_000)
    return (make_fused_chunk(config, k=2, batch_size=8, donate=False),
            (trees, batch, jnp.int32(n)), (None, batch, jnp.int32(n)),
            jax.jit(lambda s, st, size: fused_chunk_step(
                config, s, None, st, size, k=2, sample=sample,
                write_back=write_back)))


@pytest.mark.parametrize("builder", ["update", "multi_update", "fused_chunk"])
def test_uniform_replay_is_none_and_one_operand_fewer(builder, rng):
    """A caller with uniform replay passes ``None`` for the weights (the
    trees): an empty pytree, so the program the one builder compiles for
    that call has no such operand, and is bitwise ``jax.jit`` of the pure
    function called by hand."""
    config, batch = _config(), _batch(rng)
    state = init_state(config, jax.random.key(5))
    fn, per, uniform, by_hand = _uniform_program(builder, config, batch)

    def operands(*args):
        text = fn.lower(state, *args).as_text()
        main = text[text.index("func.func public @main("):]
        return main[:main.index("{\n")].count("%arg")

    dropped = 3 if builder == "fused_chunk" else 1  # a PerTrees: 3 arrays
    assert operands(*per) - operands(*uniform) == dropped

    got = fn(state, *uniform)
    want = by_hand(state, *[a for a in uniform if a is not None])
    if builder == "fused_chunk":
        assert got[1] is None and want[1] is None
    got, want = _leaves(got), _leaves(want)
    assert got.keys() == want.keys()
    for path in got:
        np.testing.assert_array_equal(got[path], want[path], path)
