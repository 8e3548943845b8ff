"""Pixel (conv-encoder) path: uint8 replay storage, PixelActor/Critic
through the jit'd update, and the full train driver on the fake pixel env
(the DM-Control-from-pixels capability, BASELINE.md config #4 — no
dm_control needed)."""

import jax
import numpy as np
import pytest

from d4pg_tpu.config import ExperimentConfig
from d4pg_tpu.envs import PixelPointEnv
from d4pg_tpu.learner import D4PGConfig, init_state, make_update
from d4pg_tpu.replay import NStepFolder, ReplayBuffer
from d4pg_tpu.replay.uniform import TransitionBatch

SHAPE = (16, 16, 3)


def test_pixel_buffer_uint8_storage(rng):
    buf = ReplayBuffer(100, SHAPE, 2)
    assert buf.obs.dtype == np.uint8
    n = 8
    batch = TransitionBatch(
        obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        action=rng.uniform(-1, 1, (n, 2)).astype(np.float32),
        reward=np.zeros(n, np.float32),
        next_obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        done=np.zeros(n, np.float32),
        discount=np.full(n, 0.99, np.float32),
    )
    buf.add(batch)
    out = buf.sample(4)
    assert out.obs.shape == (4, *SHAPE) and out.obs.dtype == np.uint8


def test_pixel_nstep_folder(rng):
    f = NStepFolder(2, 0.9, num_envs=1, obs_dim=SHAPE, act_dim=2)
    for t in range(3):
        out = f.step(
            rng.integers(0, 255, (1, *SHAPE), dtype=np.uint8),
            rng.uniform(-1, 1, (1, 2)).astype(np.float32),
            np.array([1.0]),
            rng.integers(0, 255, (1, *SHAPE), dtype=np.uint8),
            np.array([False]),
        )
    assert out.obs.shape[0] == 1 and out.obs.dtype == np.uint8
    assert out.reward[0] == pytest.approx(1.0 + 0.9)


def test_pixel_learner_update(rng):
    config = D4PGConfig(
        obs_dim=int(np.prod(SHAPE)), act_dim=2, v_min=-20.0, v_max=0.0,
        n_atoms=11, hidden=(32, 32), pixels=True, obs_shape=SHAPE,
    )
    assert config.obs_spec == SHAPE
    state = init_state(config, jax.random.key(0))
    update = make_update(config, donate=False)
    n = 8
    batch = TransitionBatch(
        obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        action=rng.uniform(-1, 1, (n, 2)).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        next_obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        done=np.zeros(n, np.float32),
        discount=np.full(n, 0.99, np.float32),
    )
    state, metrics = update(state, batch, None)
    assert np.isfinite(float(metrics["critic_loss"]))
    assert int(state.step) == 1


def test_pixel_train_end_to_end(tmp_path):
    from d4pg_tpu.train import train

    cfg = ExperimentConfig(
        env="pixel-point", max_steps=10, num_envs=2, warmup=60, n_epochs=1,
        n_cycles=1, episodes_per_cycle=1, train_steps_per_cycle=2,
        eval_trials=1, batch_size=8, memory_size=500,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-20.0, v_max=0.0, n_steps=1,
    )
    metrics = train(cfg)
    assert np.isfinite(metrics["critic_loss"])


def test_pixel_train_fused_device_replay(tmp_path):
    """uint8 frames through the fused path: device ring stores uint8, the
    in-scan gather feeds the conv encoder (which casts /255 itself), PER
    trees update from pixel TD errors."""
    from d4pg_tpu.train import train

    cfg = ExperimentConfig(
        env="pixel-point", max_steps=10, num_envs=2, warmup=60, n_epochs=1,
        n_cycles=2, episodes_per_cycle=1, train_steps_per_cycle=4,
        eval_trials=1, batch_size=8, memory_size=500,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-20.0, v_max=0.0, n_steps=1,
        replay_storage="device", fused_replay="on",
    )
    metrics = train(cfg)
    assert np.isfinite(metrics["critic_loss"])


def test_frame_stack_wrapper():
    """FrameStack: [H,W,C] -> [H,W,C*k], newest frame last, reset fills
    with k copies, uint8 preserved."""
    from d4pg_tpu.envs.fake import PixelPointEnv
    from d4pg_tpu.envs.wrappers import FrameStack

    env = FrameStack(PixelPointEnv(horizon=10, seed=0), 3)
    assert env.observation_space.shape == (16, 16, 9)
    obs, _ = env.reset()
    assert obs.shape == (16, 16, 9) and obs.dtype == np.uint8
    # reset: all three stacked frames identical
    np.testing.assert_array_equal(obs[..., :3], obs[..., 3:6])
    np.testing.assert_array_equal(obs[..., 3:6], obs[..., 6:9])
    prev = obs
    # a full-throttle action MOVES the blob, so the new frame differs from
    # the reset frame — otherwise the shift assertions below are vacuous
    obs2, *_ = env.step(np.ones(2, np.float32))
    # oldest two slots shift left; newest frame occupies the last slot
    np.testing.assert_array_equal(obs2[..., :3], prev[..., 3:6])
    np.testing.assert_array_equal(obs2[..., 3:6], prev[..., 6:9])
    assert not np.array_equal(obs2[..., 6:9], prev[..., 6:9])
    env.close()


def test_frame_stack_train_smoke(tmp_path):
    """--frame_stack 3 flows through dims/replay/encoder end to end."""
    from d4pg_tpu.config import ExperimentConfig
    from d4pg_tpu.train import infer_dims, train

    cfg = ExperimentConfig(
        env="pixel-point", max_steps=10, num_envs=2, warmup=50, n_epochs=1,
        n_cycles=1, episodes_per_cycle=1, train_steps_per_cycle=2,
        eval_trials=1, batch_size=8, memory_size=500, log_dir=str(tmp_path),
        hidden=(16, 16), n_atoms=11, v_min=-5.0, v_max=0.0,
        encoder_width=8, frame_stack=3,
    )
    obs_dim, act_dim, obs_dtype = infer_dims(cfg)
    assert obs_dim == (16, 16, 9) and obs_dtype == np.uint8
    metrics = train(cfg)
    assert np.isfinite(metrics["critic_loss"])


def test_shared_encoder_tie_and_detached_policy(rng):
    """--share_encoder (SAC-AE/DrQ): after every update the actor's
    encoder subtree is bitwise the critic's (trained by the critic loss
    alone), the policy gradient never moves it (actor Adam moments for
    the subtree stay exactly zero), and the actor MLP still trains."""
    config = D4PGConfig(
        obs_dim=int(np.prod(SHAPE)), act_dim=2, v_min=-20.0, v_max=0.0,
        n_atoms=11, hidden=(32, 32), pixels=True, obs_shape=SHAPE,
        encoder_channels=(8, 8, 8, 8), share_encoder=True,
    )
    state = init_state(config, jax.random.key(0))
    update = make_update(config, donate=False)
    n = 8
    batch = TransitionBatch(
        obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        action=rng.uniform(-1, 1, (n, 2)).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        next_obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        done=np.zeros(n, np.float32),
        discount=np.full(n, 0.99, np.float32),
    )
    prev = state
    for _ in range(2):
        state, metrics = update(state, batch, None)
    tree = jax.tree_util.tree_leaves
    for a, c in zip(tree(state.actor_params["params"]["encoder"]),
                    tree(state.critic_params["params"]["encoder"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    # encoder DID train (via the critic loss), actor MLP DID train
    assert any(
        np.any(np.asarray(a) != np.asarray(b))
        for a, b in zip(tree(prev.critic_params["params"]["encoder"]),
                        tree(state.critic_params["params"]["encoder"])))
    assert any(
        np.any(np.asarray(a) != np.asarray(b))
        for a, b in zip(tree(prev.actor_params["params"]["actor"]),
                        tree(state.actor_params["params"]["actor"])))
    # the policy loss is detached from the encoder: its Adam moments for
    # the tied subtree are exactly zero after real update steps
    mu = state.actor_opt_state[0].mu["params"]["encoder"]
    assert all(np.all(np.asarray(x) == 0) for x in tree(mu))
    assert np.isfinite(float(metrics["actor_loss"]))


def test_shared_encoder_multi_update_donation(rng):
    """Regression (round 5): the tied encoder subtree must be a COPY, not
    an alias — an aliased buffer appears in both donated param trees of
    the K-scan update and XLA rejects donating the same buffer twice
    (--share_encoder + --updates_per_dispatch>1 crashed at dispatch)."""
    from d4pg_tpu.learner import make_multi_update

    config = D4PGConfig(
        obs_dim=int(np.prod(SHAPE)), act_dim=2, v_min=-20.0, v_max=0.0,
        n_atoms=11, hidden=(32, 32), pixels=True, obs_shape=SHAPE,
        encoder_channels=(8, 8, 8, 8), share_encoder=True,
    )
    state = init_state(config, jax.random.key(0))
    update = make_multi_update(config, donate=True)
    k, n = 2, 8
    batch = TransitionBatch(
        obs=rng.integers(0, 255, (k, n, *SHAPE), dtype=np.uint8),
        action=rng.uniform(-1, 1, (k, n, 2)).astype(np.float32),
        reward=rng.standard_normal((k, n)).astype(np.float32),
        next_obs=rng.integers(0, 255, (k, n, *SHAPE), dtype=np.uint8),
        done=np.zeros((k, n), np.float32),
        discount=np.full((k, n), 0.99, np.float32),
    )
    # two consecutive donated dispatches: the second consumes the first's
    # outputs as donated inputs — where aliased subtrees blow up
    for _ in range(2):
        state, metrics = update(state, batch, None)
    jax.block_until_ready(metrics["critic_loss"])
    assert np.isfinite(np.asarray(metrics["critic_loss"])).all()
    tree = jax.tree_util.tree_leaves
    for a, c in zip(tree(state.actor_params["params"]["encoder"]),
                    tree(state.critic_params["params"]["encoder"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_shared_encoder_tie_survives_warm_moments(rng):
    """Flipping --share_encoder ON over a resumed UNshared checkpoint
    leaves stale nonzero actor-Adam moments for the encoder subtree;
    those emit decaying updates for many steps. The tie is re-asserted
    after apply_updates, so the published actor encoder stays bitwise
    the critic's anyway."""
    kw = dict(
        obs_dim=int(np.prod(SHAPE)), act_dim=2, v_min=-20.0, v_max=0.0,
        n_atoms=11, hidden=(32, 32), pixels=True, obs_shape=SHAPE,
        encoder_channels=(8, 8, 8, 8),
    )
    n = 8
    batch = TransitionBatch(
        obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        action=rng.uniform(-1, 1, (n, 2)).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        next_obs=rng.integers(0, 255, (n, *SHAPE), dtype=np.uint8),
        done=np.zeros(n, np.float32),
        discount=np.full(n, 0.99, np.float32),
    )
    # a few UNshared steps build nonzero encoder moments in the actor Adam
    unshared = D4PGConfig(**kw)
    state = init_state(unshared, jax.random.key(0))
    update = make_update(unshared, donate=False)
    for _ in range(3):
        state, _ = update(state, batch, None)
    tree = jax.tree_util.tree_leaves
    mu = state.actor_opt_state[0].mu["params"]["encoder"]
    assert any(np.any(np.asarray(x) != 0) for x in tree(mu))
    # "resume" the same state with the flag flipped on
    shared = D4PGConfig(**kw, share_encoder=True)
    update_shared = make_update(shared, donate=False)
    for _ in range(2):
        state, _ = update_shared(state, batch, None)
        # online AND target tie hold immediately after the flip — the
        # target tie must not be left to the (1-tau)^t soft-update decay
        for a, c in zip(tree(state.actor_params["params"]["encoder"]),
                        tree(state.critic_params["params"]["encoder"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        for a, c in zip(
                tree(state.target_actor_params["params"]["encoder"]),
                tree(state.target_critic_params["params"]["encoder"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_shared_encoder_requires_pixel_categorical():
    with pytest.raises(ValueError, match="share_encoder"):
        D4PGConfig(obs_dim=4, act_dim=2, share_encoder=True)


def test_shared_encoder_tied_from_init():
    """The tie holds from step 0 (targets included): a fresh shared init
    must not spend ~1/tau steps bootstrapping through a random unrelated
    actor encoder."""
    config = D4PGConfig(
        obs_dim=int(np.prod(SHAPE)), act_dim=2, v_min=-20.0, v_max=0.0,
        n_atoms=11, hidden=(32, 32), pixels=True, obs_shape=SHAPE,
        encoder_channels=(8, 8, 8, 8), share_encoder=True,
    )
    state = init_state(config, jax.random.key(0))
    tree = jax.tree_util.tree_leaves
    for params in (state.actor_params, state.target_actor_params):
        for a, c in zip(tree(params["params"]["encoder"]),
                        tree(state.critic_params["params"]["encoder"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
