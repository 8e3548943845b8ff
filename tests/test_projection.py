"""Categorical projection vs an independent NumPy oracle.

The oracle re-implements the projection spec defined by the reference's two
impls (``ddpg.py:122-140`` and ``:142-185``): per-atom Bellman map, clip to
support, linear interpolation of mass between floor/ceil bins, terminal
transitions collapsing to a delta at clip(r).
"""

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from d4pg_tpu.core import CategoricalSupport, categorical_projection
from d4pg_tpu.core.losses import expected_q


def oracle_projection(v_min, v_max, n_atoms, probs, rewards, discounts):
    """Straightforward per-sample, per-atom scatter projection (numpy)."""
    delta = (v_max - v_min) / (n_atoms - 1)
    atoms = v_min + delta * np.arange(n_atoms)
    out = np.zeros_like(probs)
    b_size = probs.shape[0]
    for i in range(b_size):
        for a in range(n_atoms):
            tz = np.clip(rewards[i] + discounts[i] * atoms[a], v_min, v_max)
            b = (tz - v_min) / delta
            l, u = int(np.floor(b)), int(np.ceil(b))
            if l == u:
                out[i, l] += probs[i, a]
            else:
                out[i, l] += probs[i, a] * (u - b)
                out[i, u] += probs[i, a] * (b - l)
    return out


@pytest.fixture
def support():
    return CategoricalSupport(v_min=-10.0, v_max=10.0, n_atoms=51)


def random_dist(rng, shape):
    p = rng.random(shape)
    return p / p.sum(axis=-1, keepdims=True)


@pytest.mark.parametrize("b", [1, 64, 100])
def test_matches_oracle(rng, support, b):
    probs = random_dist(rng, (b, support.n_atoms)).astype(np.float32)
    rewards = rng.normal(0, 5, b).astype(np.float32)
    dones = (rng.random(b) < 0.3).astype(np.float32)
    discounts = (0.99**3) * (1.0 - dones)

    got = np.asarray(categorical_projection(support, probs, rewards, discounts))
    want = oracle_projection(
        support.v_min, support.v_max, support.n_atoms, probs, rewards, discounts
    )
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_rows_sum_to_one(rng, support):
    probs = random_dist(rng, (64, support.n_atoms))
    rewards = rng.normal(0, 20, 64)  # many hit the clip boundaries
    discounts = np.full(64, 0.99)
    got = np.asarray(categorical_projection(support, probs, rewards, discounts))
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-6)
    assert (got >= -1e-7).all()


def test_terminal_collapses_to_delta_at_reward(support):
    """discount=0 must reproduce the reference's terminal overwrite
    (``ddpg.py:165-181``): a delta (or two-bin interpolation) at clip(r)."""
    probs = np.full((3, support.n_atoms), 1.0 / support.n_atoms)
    rewards = np.array([0.0, -10.0, 3.1])  # exact bin, clip edge, fractional
    discounts = np.zeros(3)
    got = np.asarray(categorical_projection(support, probs, rewards, discounts))
    atoms = np.asarray(support.atoms)
    np.testing.assert_allclose(got.sum(-1), 1.0, atol=1e-6)
    # projected mean must equal clip(r)
    np.testing.assert_allclose((got * atoms).sum(-1), rewards, atol=1e-5)
    # exact-bin cases are pure deltas
    assert got[0, 25] == pytest.approx(1.0)
    assert got[1, 0] == pytest.approx(1.0)


def test_identity_when_reward_zero_discount_one(rng, support):
    """r=0, discount=1 leaves distributions unchanged."""
    probs = random_dist(rng, (8, support.n_atoms))
    got = np.asarray(
        categorical_projection(support, probs, np.zeros(8), np.ones(8))
    )
    np.testing.assert_allclose(got, probs, atol=1e-6)


def test_mean_contraction(rng, support):
    """Projected mean ~= r + gamma^n * E[Z] when no clipping occurs."""
    probs = random_dist(rng, (16, support.n_atoms))
    rewards = rng.normal(0, 0.5, 16)
    discounts = np.full(16, 0.5)
    got = categorical_projection(support, jnp.asarray(probs), rewards, discounts)
    want = rewards + discounts * np.asarray(
        expected_q(support, jnp.asarray(probs))
    )
    # small interpolation error is expected (projection is not mean-exact
    # once mass is redistributed, but with these scales it's tight)
    np.testing.assert_allclose(np.asarray(expected_q(support, got)), want, atol=0.05)


def test_critic_loss_gradient_matches_oracle_and_stops_at_targets(rng):
    """The critic loss is ``mean(w * -sum(stop_gradient(proj) * log q))``:
    its gradient into the critic equals the gradient of that expression
    with the ORACLE's projection held constant, and nothing flows into the
    networks that produced the Bellman target."""
    from d4pg_tpu.learner import D4PGConfig, init_state
    from d4pg_tpu.learner.update import _critic_loss_fn
    from d4pg_tpu.replay.uniform import TransitionBatch

    b, obs_dim, act_dim = 64, 6, 2
    config = D4PGConfig(obs_dim=obs_dim, act_dim=act_dim, v_min=-5.0,
                        v_max=0.0, n_atoms=31, hidden=(16, 16))
    state = init_state(config, jax.random.key(0))
    done = (rng.random(b) < 0.3).astype(np.float32)
    batch = TransitionBatch(
        obs=rng.standard_normal((b, obs_dim)).astype(np.float32),
        action=rng.uniform(-1, 1, (b, act_dim)).astype(np.float32),
        reward=rng.uniform(-6, 1, b).astype(np.float32),
        next_obs=rng.standard_normal((b, obs_dim)).astype(np.float32),
        done=done,
        discount=(0.99 * (1.0 - done)).astype(np.float32),
    )
    w = jnp.asarray(rng.random(b), jnp.float32)  # IS-weighted mean

    def loss(critic_params, targets):
        st = state._replace(target_critic_params=targets[0],
                            target_actor_params=targets[1])
        return _critic_loss_fn(config, critic_params, st, batch, w,
                               jax.random.key(1))[0]

    got, into_targets = jax.grad(loss, argnums=(0, 1))(
        state.critic_params,
        (state.target_critic_params, state.target_actor_params))
    for leaf in jax.tree_util.tree_leaves(into_targets):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)

    actor, critic = config.build_actor(), config.build_critic()
    target = critic.apply(
        state.target_critic_params, batch.next_obs,
        actor.apply(state.target_actor_params, batch.next_obs))
    proj = oracle_projection(config.v_min, config.v_max, config.n_atoms,
                             np.asarray(target), batch.reward, batch.discount)

    def oracle_loss(critic_params):
        q = critic.apply(critic_params, batch.obs, batch.action)
        return jnp.mean(w * -jnp.sum(proj * jnp.log(q + 1e-10), axis=-1))

    want = jax.grad(oracle_loss)(state.critic_params)
    # the gradient is not vacuous
    assert max(float(jnp.abs(g).max())
               for g in jax.tree_util.tree_leaves(want)) > 1e-4
    for g, o in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(o),
                                   atol=1e-5, rtol=1e-4)
