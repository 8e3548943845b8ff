"""Pallas kernel tests (interpret mode on the CPU backend).

The einsum projection (core/distribution.py, itself oracle-tested against
the reference's per-atom loop in test_projection.py) is the oracle here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.core.distribution import CategoricalSupport, categorical_projection
from d4pg_tpu.ops.projection import projection_pallas


def _rand_dist(rng, b, a):
    p = rng.random((b, a))
    return (p / p.sum(-1, keepdims=True)).astype(np.float32)


@pytest.mark.parametrize("batch", [1, 64, 100])
def test_pallas_projection_matches_einsum(rng, batch):
    sup = CategoricalSupport(-10.0, 0.0, 51)
    p = jnp.asarray(_rand_dist(rng, batch, 51))
    r = jnp.asarray(rng.uniform(-12, 2, batch), jnp.float32)  # incl. out-of-range
    done = rng.random(batch) < 0.3
    d = jnp.asarray((0.99**3) * ~done, jnp.float32)
    ref = categorical_projection(sup, p, r, d)
    out = projection_pallas(sup, p, r, d, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out).sum(-1), 1.0, atol=1e-5)


def test_pallas_projection_terminal_delta(rng):
    """Terminal transitions (discount 0) collapse to a delta at clip(r)."""
    sup = CategoricalSupport(0.0, 10.0, 11)
    p = jnp.asarray(_rand_dist(rng, 8, 11))
    r = jnp.asarray(np.full(8, 5.0), jnp.float32)
    d = jnp.zeros(8, jnp.float32)
    out = np.asarray(projection_pallas(sup, p, r, d, True))
    want = np.zeros((8, 11), np.float32)
    want[:, 5] = 1.0  # atom exactly at 5.0
    np.testing.assert_allclose(out, want, atol=1e-6)


def test_pallas_ce_forward_matches_einsum_ce(rng):
    """Fused projection+cross-entropy == einsum projection then CE."""
    from d4pg_tpu.core.losses import cross_entropy_per_sample
    from d4pg_tpu.ops.projection_ce import projection_ce_pallas

    sup = CategoricalSupport(-10.0, 0.0, 51)
    for batch in (1, 64, 100):
        p = jnp.asarray(_rand_dist(rng, batch, 51))
        q = jnp.asarray(_rand_dist(rng, batch, 51))
        r = jnp.asarray(rng.uniform(-12, 2, batch), jnp.float32)
        done = rng.random(batch) < 0.3
        d = jnp.asarray((0.99**3) * ~done, jnp.float32)
        ref = cross_entropy_per_sample(categorical_projection(sup, p, r, d), q)
        out = projection_ce_pallas(sup, p, r, d, q, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-5)


def test_pallas_ce_gradient_matches_stop_gradient_reference(rng):
    """The custom VJP must equal autodiff of CE(stop_gradient(proj), q) —
    the exact gradient convention of learner/update.py's critic loss."""
    from d4pg_tpu.core.losses import cross_entropy_per_sample
    from d4pg_tpu.ops.projection_ce import projection_ce_pallas

    sup = CategoricalSupport(-5.0, 0.0, 31)
    batch = 64
    p = jnp.asarray(_rand_dist(rng, batch, 31))
    q = jnp.asarray(_rand_dist(rng, batch, 31))
    r = jnp.asarray(rng.uniform(-6, 1, batch), jnp.float32)
    d = jnp.asarray(np.full(batch, 0.99), jnp.float32)
    w = jnp.asarray(rng.random(batch), jnp.float32)  # IS-weighted mean

    def ref_loss(q_):
        proj = jax.lax.stop_gradient(categorical_projection(sup, p, r, d))
        return jnp.mean(w * cross_entropy_per_sample(proj, q_))

    def fused_loss(q_):
        return jnp.mean(w * projection_ce_pallas(sup, p, r, d, q_, True))

    g_ref = jax.grad(ref_loss)(q)
    g_fused = jax.grad(fused_loss)(q)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               atol=1e-5, rtol=1e-5)
    # and no gradient leaks through the Bellman operands
    gp = jax.grad(lambda p_: jnp.sum(
        projection_ce_pallas(sup, p_, r, d, q, True)))(p)
    np.testing.assert_array_equal(np.asarray(gp), 0.0)


def test_update_step_pallas_ce_matches_einsum(rng):
    """One full update with --projection pallas_ce equals the einsum path
    (same batch, same seed) to float tolerance."""
    import warnings

    from d4pg_tpu.learner import D4PGConfig, init_state, make_update
    from d4pg_tpu.replay.uniform import TransitionBatch

    b, obs_dim, act_dim = 64, 6, 2
    batch = TransitionBatch(
        obs=rng.standard_normal((b, obs_dim)).astype(np.float32),
        action=rng.uniform(-1, 1, (b, act_dim)).astype(np.float32),
        reward=rng.standard_normal(b).astype(np.float32),
        next_obs=rng.standard_normal((b, obs_dim)).astype(np.float32),
        done=np.zeros(b, np.float32),
        discount=np.full(b, 0.99, np.float32),
    )
    weights = np.ones(b, np.float32)
    outs = {}
    for proj in ("einsum", "pallas_ce"):
        config = D4PGConfig(obs_dim=obs_dim, act_dim=act_dim, v_min=-5.0,
                            v_max=0.0, n_atoms=11, hidden=(16, 16),
                            projection=proj)
        state = init_state(config, jax.random.key(0))
        update = make_update(config, donate=False, use_is_weights=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # interpret-mode warning on CPU
            state, metrics = update(state, batch, weights)
        outs[proj] = (state, metrics)
    np.testing.assert_allclose(
        float(outs["pallas_ce"][1]["critic_loss"]),
        float(outs["einsum"][1]["critic_loss"]), rtol=1e-5)
    for a, b_ in zip(jax.tree_util.tree_leaves(outs["einsum"][0].critic_params),
                     jax.tree_util.tree_leaves(outs["pallas_ce"][0].critic_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=1e-5, rtol=1e-4)


def test_random_shift_properties(rng):
    """DrQ random shift: every output row is a valid crop of its padded
    input, dtype/shape preserved, deterministic per key, varied across
    the batch."""
    from d4pg_tpu.ops.augment import random_shift

    b, h, w, c, pad = 16, 8, 8, 3, 2
    imgs = rng.integers(0, 255, (b, h, w, c), dtype=np.uint8)
    out = np.asarray(random_shift(jax.random.key(0), jnp.asarray(imgs), pad))
    assert out.shape == imgs.shape and out.dtype == np.uint8
    # each row must equal one of the (2*pad+1)^2 crops of its padded self
    offsets_seen = set()
    for i in range(b):
        padded = np.pad(imgs[i], ((pad, pad), (pad, pad), (0, 0)),
                        mode="edge")
        found = None
        for dy in range(2 * pad + 1):
            for dx in range(2 * pad + 1):
                if np.array_equal(out[i], padded[dy:dy + h, dx:dx + w]):
                    found = (dy, dx)
                    break
            if found:
                break
        assert found is not None, f"row {i} is not a crop of its input"
        offsets_seen.add(found)
    assert len(offsets_seen) > 1  # shifts actually vary across the batch
    # deterministic per key
    out2 = np.asarray(random_shift(jax.random.key(0), jnp.asarray(imgs), pad))
    np.testing.assert_array_equal(out, out2)
    # pad=0 is the identity
    np.testing.assert_array_equal(
        np.asarray(random_shift(jax.random.key(1), jnp.asarray(imgs), 0)),
        imgs)


def _shift_oracle(key, imgs, pad):
    """NumPy crop of an edge-padded copy per image, offsets drawn one
    sample at a time with the ``fold_in`` / ``randint`` of the docstring."""
    b, h, w, _ = imgs.shape
    out = np.empty_like(imgs)
    for i in range(b):
        dy, dx = np.asarray(jax.random.randint(
            jax.random.fold_in(key, i), (2,), 0, 2 * pad + 1))
        padded = np.pad(imgs[i], ((pad, pad), (pad, pad), (0, 0)),
                        mode="edge")
        out[i] = padded[dy:dy + h, dx:dx + w]
    return out


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("pad", [1, 2, 4])
@pytest.mark.parametrize("shape", [(32, 84, 84, 9), (16, 8, 8, 3),
                                   (7, 13, 11, 3), (8, 12, 12, 3)])
def test_random_shift_matches_numpy_oracle(rng, shape, pad, dtype, jit):
    """Bit for bit: the batched shift moves every image where a per-image
    crop at the same offsets moves it (benchmark/reference.py draws them
    the same way, so anything else fails the pixel cell's loss gaps)."""
    from d4pg_tpu.ops.augment import random_shift

    imgs = (rng.integers(0, 256, shape).astype(dtype) if dtype == np.uint8
            else rng.standard_normal(shape).astype(dtype))
    key = jax.random.key(shape[0] + pad)
    fn = jax.jit(random_shift, static_argnums=2) if jit else random_shift
    out = np.asarray(fn(key, jnp.asarray(imgs), pad))
    assert out.dtype == dtype
    np.testing.assert_array_equal(out, _shift_oracle(key, imgs, pad))


def test_random_shift_op_count_is_independent_of_batch():
    """What the speed rests on: the same number of whole-batch operations
    at any batch size, and none that indexes per sample (a vmapped
    dynamic crop lowers to ``stablehlo.gather``, which the TPU runs as
    one one-row update per image)."""
    from d4pg_tpu.ops.augment import random_shift

    def lowered(b):
        imgs = jax.ShapeDtypeStruct((b, 84, 84, 9), jnp.uint8)
        return jax.jit(random_shift, static_argnums=2).lower(
            jax.random.key(0), imgs, 4).as_text()

    small, large = lowered(8), lowered(64)
    assert small.count("stablehlo.") == large.count("stablehlo.")
    for text in (small, large):
        for op in ("gather", "dynamic_slice", "dynamic_update_slice"):
            assert "stablehlo." + op not in text, op


def test_random_shift_sharded_over_data_equals_unsharded(rng):
    """Elementwise in the batch axis: with the batch split over the
    ``data`` axis of the 8-device mesh every shard sees the crops the
    single-device computation sees."""
    from jax.sharding import NamedSharding, PartitionSpec
    from d4pg_tpu.ops.augment import random_shift
    from d4pg_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data_parallel=8, model_parallel=1))
    imgs = rng.integers(0, 256, (16, 12, 12, 3)).astype(np.uint8)
    key = jax.random.key(5)
    fn = jax.jit(random_shift, static_argnums=2)
    want = np.asarray(fn(key, jnp.asarray(imgs), 4))
    sharded = jax.device_put(imgs, NamedSharding(mesh, PartitionSpec("data")))
    got = fn(key, sharded, 4)
    assert got.sharding.is_equivalent_to(sharded.sharding, 4)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_update_step_with_shift_augmentation(rng):
    """--augment shift runs through the full jit'd pixel update: finite
    losses, and the augmented update diverges from the unaugmented one
    (the views differ) while non-pixel configs reject the flag."""
    from d4pg_tpu.learner import D4PGConfig, init_state, make_update
    from d4pg_tpu.replay.uniform import TransitionBatch

    b, hw, ch = 8, 12, 3
    batch = TransitionBatch(
        obs=rng.integers(0, 255, (b, hw, hw, ch), dtype=np.uint8),
        action=rng.uniform(-1, 1, (b, 2)).astype(np.float32),
        reward=rng.standard_normal(b).astype(np.float32),
        next_obs=rng.integers(0, 255, (b, hw, hw, ch), dtype=np.uint8),
        done=np.zeros(b, np.float32),
        discount=np.full(b, 0.99, np.float32),
    )
    losses = {}
    for aug in ("none", "shift"):
        config = D4PGConfig(
            obs_dim=hw * hw * ch, act_dim=2, pixels=True,
            obs_shape=(hw, hw, ch), encoder_channels=(8,) * 4,
            v_min=-5.0, v_max=0.0, n_atoms=11, hidden=(16, 16),
            augment=aug)
        state = init_state(config, jax.random.key(0))
        update = make_update(config, donate=False, use_is_weights=False)
        state, metrics = update(state, batch)
        assert np.isfinite(float(metrics["critic_loss"]))
        losses[aug] = float(metrics["critic_loss"])
    assert losses["none"] != losses["shift"]
    with pytest.raises(ValueError, match="pixel"):
        D4PGConfig(obs_dim=6, act_dim=2, augment="shift")
