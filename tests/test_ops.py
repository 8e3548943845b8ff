"""``ops/augment.py``: the DrQ random shift against a per-sample crop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


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
        update = make_update(config, donate=False)
        state, metrics = update(state, batch, None)
        assert np.isfinite(float(metrics["critic_loss"]))
        losses[aug] = float(metrics["critic_loss"])
    assert losses["none"] != losses["shift"]
    with pytest.raises(ValueError, match="pixel"):
        D4PGConfig(obs_dim=6, act_dim=2, augment="shift")
