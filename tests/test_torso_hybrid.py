"""LFM2's layers as a torso (``lfm2``: ``models/torso.py`` over
``ops/short_conv.py``) at a small size on the CPU against the plain
reference (``benchmark/reference_hybrid.py``): each kind of layer, the whole
gradient step with the routing bias after K steps; the gated convolution and
its gradients against ``numpy``'s explicit sum; the bias's selection-only
role, its zero gradient and Adam moments, the ``bias_swapped`` counter
against brute force; 64-wide heads through the kernel path the chip takes;
the grouped product with K split over tiles; the seeded trees of the two
older models bit-equal to the parent's; the normal path through
``train.main``. Sizes: hidden 64, 4 query heads on 2 key/value heads of 16,
3 taps, a dense layer of width 96, 8 experts top-2 of width 32, 32 tokens:
``conv`` dense, ``full_attention``, ``conv``."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_hybrid as rh
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.update import update_step
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.ops import attention as attn_ops
from d4pg_tpu.ops import grouped as grouped_ops
from d4pg_tpu.ops import short_conv
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROPE = {"full_attention": {"rope_type": "default", "rope_theta": 1000000}}
SMALL = dict(
    name="lfm2", tokens=32, vocab_rows=64, bins=16, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_types=["conv", "full_attention", "conv"], qk_norm=True,
    conv_L_cache=3, num_dense_layers=1, intermediate_size=96,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    experts_held=[2, 6], router_scores="sigmoid", use_expert_bias=True,
    routed_scaling_factor=1.0, bias_update_rate=1e-3, rms_norm_eps=1e-5,
    rope_parameters=ROPE)
MODEL = dict(obs_dim=32, act_dim=3, hidden=(32, 32, 32), n_atoms=11,
             v_min=0.0, v_max=10.0, torso=SMALL)
B = 4
GAMMA = SMALL["bias_update_rate"]


def small_config(**torso_over):
    return D4PGConfig(**{**MODEL, "torso": {**SMALL, **torso_over}})


def small_batch(seed=1):
    k = jax.random.split(jax.random.key(seed), 4)
    return TransitionBatch(
        obs=3.0 * jax.random.normal(k[0], (B, 32)),
        action=jax.random.uniform(k[1], (B, 3), minval=-1, maxval=1),
        reward=jax.random.normal(k[2], (B,)),
        next_obs=jax.random.normal(k[3], (B, 32)),
        done=jnp.zeros((B,)), discount=jnp.full((B,), 0.99))


def seeded_state(config, seed=0):
    """``init_state`` with the norms' gains moved off 1 and the routing
    biases off 0 by whole multiples of gamma, so that a test sees them."""
    state = init_state(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 1000))

    def move(path, x):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return x + 0.3 * jax.random.normal(next(keys), x.shape)
        if name == "bias":  # scores differ by ~0.1 at this size
            return 100 * GAMMA * jax.random.randint(
                next(keys), x.shape, -2, 3).astype(jnp.float32)
        return x

    critic = jax.tree_util.tree_map_with_path(move, state.critic_params)
    return state._replace(
        critic_params=critic,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic))


def tree_gap(a, b):
    diff = jax.tree_util.tree_map(lambda x, y: x - y, a, b)
    return float(np.max(reference.leaf_norms(diff)
                        / np.maximum(reference.leaf_norms(b), 1e-12)))


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()[:16]


# -- the seam -----------------------------------------------------------------
def test_spec_takes_the_new_layer_type_feed_forward_and_router_as_data():
    config = small_config()
    spec = config.torso
    assert spec.expert_layers == (1, 2) and spec.conv_L_cache == 3
    assert hash(config) == hash(small_config())
    assert type(config.build_critic().torso) is torso_lib.TORSOS["mellum2"]
    layers = init_state(config, jax.random.key(0)).critic_params[
        "params"]["torso"]
    # a layer has only the leaves it has
    assert set(layers["layer_0"]) == {
        "conv_norm", "in_proj", "conv", "out_proj", "mlp_norm", "w1", "w3",
        "w2"}
    assert set(layers["layer_1"]) == {
        "attn_norm", "q", "k", "v", "o", "q_norm", "k_norm", "moe_norm",
        "router", "gate", "up", "down"}
    assert set(layers["layer_2"]) == {
        "conv_norm", "in_proj", "conv", "out_proj", "moe_norm", "router",
        "gate", "up", "down"}
    assert layers["layer_0"]["in_proj"]["kernel"].shape == (64, 192)
    assert layers["layer_0"]["conv"]["kernel"].shape == (64, 3)
    assert layers["layer_0"]["w1"]["kernel"].shape == (64, 96)
    assert set(layers["layer_1"]["router"]) == {"kernel", "bias"}
    assert layers["layer_1"]["router"]["bias"].shape == (8,)
    assert layers["layer_1"]["router"]["bias"].dtype == jnp.float32
    # the taps at their own fan-in, 3
    assert float(jnp.std(layers["layer_2"]["conv"]["kernel"])) \
        == pytest.approx(3 ** -0.5, rel=0.2)
    with pytest.raises(ValueError, match="conv_L_cache"):
        small_config(conv_L_cache=0)
    with pytest.raises(ValueError, match="intermediate_size"):
        small_config(intermediate_size=0)
    with pytest.raises(ValueError, match="no expert layer"):
        small_config(num_dense_layers=3)
    with pytest.raises(ValueError, match="router_scores"):
        small_config(router_scores="tanh")
    with pytest.raises(ValueError, match="sigmoid router"):
        small_config(router_scores="softmax")


@pytest.mark.parametrize("which, want", [
    ("mellum2.init", "67642a3108d252ba"), ("keye2.init", "1a3dfac60acd2b57"),
    ("humanoid-mellum2-ep4.seeded", "85ec67476d7952e3"),
    ("humanoid-keye2-ep8.seeded", "42f46311268478e9")])
def test_the_older_models_seeded_trees_are_the_parents_bit_for_bit(which,
                                                                   want):
    """Digests taken on the parent commit (2f9984f): ``init_state`` of the
    two older models' test configurations and the benchmark's seeded weights
    at their rehearsal sizes."""
    name, kind = which.split(".")
    if kind == "init":
        import importlib
        import sys

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        mod = importlib.import_module(
            "test_torso" if name == "mellum2" else "test_torso_sparse")
        tree = init_state(mod.small_config(),
                          jax.random.key(0)).critic_params
    else:
        from benchmark import cellbuild
        from benchmark.drivers import learner_static_torso as drv

        config = cellbuild.learner_config(cellbuild.load_config(name, True))
        tree = jax.jit(lambda s: drv.seeded_params(config, s))(
            jnp.uint32(12345))[1]
    assert digest(tree) == want


# -- the gated short convolution ---------------------------------------------
def numpy_conv(bcu, taps):
    """The explicit sum, one position, channel and tap at a time."""
    bcu, taps = np.asarray(bcu, np.float64), np.asarray(taps, np.float64)
    t_len, c = bcu.shape[0], taps.shape[0]
    b, cc, u = bcu[:, :c], bcu[:, c:2 * c], bcu[:, 2 * c:]
    g = b * u
    out = np.zeros((t_len, c))
    n_taps = taps.shape[1]
    for t in range(t_len):
        for j in range(n_taps):
            src = t - (n_taps - 1 - j)
            if src >= 0:
                out[t] += taps[:, j] * g[src]
    return cc * out


def test_the_convolution_and_its_gradients_match_numpys_explicit_sum():
    k = jax.random.split(jax.random.key(0), 3)
    bcu = jax.random.normal(k[0], (12, 15))
    taps = jax.random.normal(k[1], (5, 3))
    cot = jax.random.normal(k[2], (12, 5))
    got = short_conv.gated_short_conv(bcu, taps)
    np.testing.assert_allclose(np.asarray(got), numpy_conv(bcu, taps),
                               rtol=1e-5, atol=1e-6)
    # position 0 reads two zeros, position 1 one: only the last taps count
    b, c, u = (np.asarray(x) for x in jnp.split(bcu, 3, axis=-1))
    w = np.asarray(taps)
    np.testing.assert_allclose(np.asarray(got[0]), c[0] * w[:, 2] * b[0]
                               * u[0], rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got[1]),
        c[1] * (w[:, 2] * b[1] * u[1] + w[:, 1] * b[0] * u[0]), rtol=1e-5)
    # gradients against finite differences of the explicit sum
    loss = lambda bcu, taps: jnp.sum(  # noqa: E731
        short_conv.gated_short_conv(bcu, taps) * cot)
    d_bcu, d_taps = jax.grad(loss, argnums=(0, 1))(bcu, taps)
    ref = lambda bcu, taps: float(  # noqa: E731
        np.sum(numpy_conv(bcu, taps) * np.asarray(cot, np.float64)))
    eps = 1e-4
    bcu64, taps64 = np.asarray(bcu, np.float64), np.asarray(taps, np.float64)
    for idx in [(0, 0), (3, 7), (11, 14), (5, 10), (10, 2)]:
        hi, lo = bcu64.copy(), bcu64.copy()
        hi[idx] += eps
        lo[idx] -= eps
        assert float(d_bcu[idx]) == pytest.approx(
            (ref(hi, taps64) - ref(lo, taps64)) / (2 * eps), rel=1e-3,
            abs=1e-4)
    for idx in [(0, 0), (2, 1), (4, 2)]:
        hi, lo = taps64.copy(), taps64.copy()
        hi[idx] += eps
        lo[idx] -= eps
        assert float(d_taps[idx]) == pytest.approx(
            (ref(bcu64, hi) - ref(bcu64, lo)) / (2 * eps), rel=1e-3,
            abs=1e-4)
    # the reference's convolution is the same sum
    np.testing.assert_allclose(
        np.asarray(c * np.asarray(rh.short_conv(jnp.asarray(b * u), taps))),
        numpy_conv(bcu, taps), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("t", [0, 1, 5, 11])
def test_no_position_reads_a_later_one(t):
    """Perturb ``b``, ``c`` and ``u`` at ``t``: nothing before ``t`` moves,
    ``t`` ... ``t + 2`` move and nothing after (three taps)."""
    bcu = jax.random.normal(jax.random.key(1), (12, 15))
    taps = jax.random.normal(jax.random.key(2), (5, 3))
    base = np.asarray(short_conv.gated_short_conv(bcu, taps))
    moved = np.asarray(short_conv.gated_short_conv(
        bcu.at[t].add(0.5), taps))
    changed = np.any(moved != base, axis=1)
    assert not changed[:t].any()
    assert changed[t] and not changed[t + 3:].any()
    # and in the gradient: no output before t has a part in the input at t
    jac = jax.jacobian(lambda x: short_conv.gated_short_conv(x, taps))(bcu)
    assert not np.asarray(jac[:t, :, t, :]).any()
    assert np.asarray(jac[t:t + 3, :, t, :]).any()


# -- the router and its bias --------------------------------------------------
def _router_inputs(seed=0):
    spec = small_config().torso
    k = jax.random.split(jax.random.key(seed), 3)
    h = jax.random.normal(k[0], (32, 64))
    router = {"kernel": jax.random.normal(k[1], (64, 8)) / 8,
              "bias": 0.1 * jax.random.normal(k[2], (8,))}
    return spec, h, router


def test_the_bias_enters_the_selection_and_not_the_weights():
    spec, h, router = _router_inputs()
    w, e, stats = torso_lib.route(spec, h, router)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(h, router["kernel"],
                                          precision="highest")), np.float64)
    biased = s + np.asarray(router["bias"], np.float64)
    swapped = 0
    for t in range(32):
        chosen = set(np.argsort(-biased[t])[:2].tolist())
        assert set(np.asarray(e[t]).tolist()) == chosen
        # the weights are the scores, renormalised with the 1e-6
        want = s[t, np.asarray(e[t])]
        np.testing.assert_allclose(np.asarray(w[t]),
                                   want / (want.sum() + 1e-6), rtol=1e-5)
        swapped += len(chosen - set(np.argsort(-s[t])[:2].tolist()))
    assert 0 < swapped == int(stats["bias_swapped"])
    assert int(stats["route_counts"].sum()) == 64
    # a bias equal on every expert changes nothing at all
    flat = {**router, "bias": jnp.full((8,), 0.25)}
    zero = {**router, "bias": jnp.zeros((8,))}
    w1, e1, st1 = torso_lib.route(spec, h, flat)
    w0, e0, st0 = torso_lib.route(spec, h, zero)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e0))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w0))
    assert int(st1["bias_swapped"]) == int(st0["bias_swapped"]) == 0
    # the reference routes alike
    rw, re_, rcounts, rswapped = rh.route(SMALL, h, router)
    np.testing.assert_array_equal(np.asarray(re_), np.asarray(e))
    np.testing.assert_allclose(np.asarray(rw), np.asarray(w), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(rcounts),
                                  np.asarray(stats["route_counts"]))
    assert int(rswapped) == swapped
    # equal scores: a tie goes to the lower index, as top_k breaks it, so
    # the unbiased selection is experts 0 and 1 and the bias swaps both
    tied = {"kernel": jnp.zeros((64, 8)),
            "bias": jnp.zeros((8,)).at[jnp.asarray([3, 6])].set(0.1)}
    _w, e_t, st_t = torso_lib.route(spec, h, tied)
    assert set(np.asarray(e_t).reshape(-1).tolist()) == {3, 6}
    assert int(st_t["bias_swapped"]) == 64 == int(rh.route(SMALL, h, tied)[3])
    half = {**tied, "bias": jnp.zeros((8,)).at[jnp.asarray([1, 6])].set(0.1)}
    assert int(torso_lib.route(spec, h, half)[2]["bias_swapped"]) == 32
    # routed_scaling_factor scales the weights and nothing else
    scaled = small_config(routed_scaling_factor=2.5).torso
    w2, e2, _ = torso_lib.route(scaled, h, router)
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(e))
    np.testing.assert_allclose(np.asarray(w2), 2.5 * np.asarray(w),
                               rtol=1e-6)


def test_the_bias_has_no_gradient_and_adam_leaves_it_where_it_is():
    config = small_config()
    state = seeded_state(config, 3)
    new, metrics = jax.jit(lambda s, b: update_step(
        config, s, b, jnp.ones((B,))))(state, small_batch())
    assert int(np.asarray(metrics["bias_swapped"]).sum()) > 0
    for i in config.torso.expert_layers:
        old = state.critic_params["params"]["torso"][f"layer_{i}"]["router"]
        mu = new.critic_opt_state[0].mu["params"]["torso"][f"layer_{i}"][
            "router"]
        nu = new.critic_opt_state[0].nu["params"]["torso"][f"layer_{i}"][
            "router"]
        assert float(jnp.max(jnp.abs(mu["bias"]))) == 0.0
        assert float(jnp.max(jnp.abs(nu["bias"]))) == 0.0
        assert float(jnp.max(jnp.abs(mu["kernel"]))) > 0.0
        # what moved it is the rule alone: gamma * sign(mean(n) - n)
        n = np.asarray(metrics["route_counts"][
            config.torso.expert_layers.index(i)], np.float64)
        got = np.asarray(new.critic_params["params"]["torso"][f"layer_{i}"][
            "router"]["bias"]) - np.asarray(old["bias"])
        np.testing.assert_allclose(got, GAMMA * np.sign(n.mean() - n),
                                   atol=1e-7)
        # and the target's follows by the soft update
        target = np.asarray(new.target_critic_params["params"]["torso"][
            f"layer_{i}"]["router"]["bias"]) - np.asarray(old["bias"])
        np.testing.assert_allclose(target, config.tau * got, atol=1e-8)
    # the gradient itself, not only its moments
    critic = config.build_critic()
    batch = small_batch()
    grads = jax.grad(lambda p: jnp.sum(
        critic.latent(p, batch.obs, train=True)[0] ** 2))(
        state.critic_params)
    for i in config.torso.expert_layers:
        g = grads["params"]["torso"][f"layer_{i}"]["router"]
        assert float(jnp.max(jnp.abs(g["bias"]))) == 0.0
        assert float(jnp.max(jnp.abs(g["kernel"]))) > 0.0


def test_a_torso_without_a_bias_is_handed_back_by_balance():
    import test_torso

    config = test_torso.small_config()
    critic = config.build_critic()
    params = init_state(config, jax.random.key(0)).critic_params
    assert critic.balance(params, jnp.zeros((4, 8), jnp.int32)) \
        ["params"]["torso"] is params["params"]["torso"]


# -- each kind of layer and the whole step against the reference -------------
@pytest.mark.parametrize("index, layer_type, dense", [
    (0, "conv", True), (1, "full_attention", False), (2, "conv", False)])
def test_each_kind_of_layer_matches_the_reference(index, layer_type, dense):
    config = small_config()
    torso = config.build_critic().torso
    p = seeded_state(config, 5).critic_params["params"]["torso"][
        f"layer_{index}"]
    x = jax.random.normal(jax.random.key(index), (32, 64))
    got, stats, _sel = torso._sequence(p, x, layer_type, dense, True)
    want, ref_stats = rh.layer(rh.EXACT_OPS, SMALL, p, x, layer_type, dense)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    assert bool(stats) == bool(ref_stats) == (not dense)
    if stats:
        np.testing.assert_array_equal(np.asarray(stats["route_counts"]),
                                      np.asarray(ref_stats[0]))
        assert int(stats["bias_swapped"]) == int(ref_stats[1])


def test_forward_pass_and_counters_match_the_reference():
    config = small_config()
    state = seeded_state(config, 2)
    batch = small_batch()
    latent, aux = config.build_critic().latent(state.critic_params,
                                               batch.obs, train=True)
    z, counts, swapped = rh.torso(rh.EXACT_OPS, SMALL,
                                  state.critic_params["params"]["torso"],
                                  batch.obs)
    np.testing.assert_allclose(np.asarray(latent), np.asarray(z), rtol=2e-4,
                               atol=2e-5)
    assert aux["route_counts"].shape == (2, 8)  # the expert layers alone
    assert aux["bias_swapped"].shape == (2,)
    np.testing.assert_array_equal(np.asarray(aux["route_counts"]),
                                  np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(aux["bias_swapped"]),
                                  np.asarray(swapped))
    assert int(np.asarray(swapped).sum()) > 0
    assert int(np.asarray(counts).sum()) == 2 * B * 32 * 2


def test_whole_steps_match_the_reference_the_bias_included():
    """Three steps: losses, TD errors, counters, the gradient (Adam's first
    moment after one step is 0.1 of it), the parameters and the biases."""
    config = small_config()
    state = seeded_state(config, 1)
    cfg = reference.model_cfg({**MODEL, "torso": SMALL})
    st = rh.init(state.actor_params, state.critic_params)
    key = jax.random.key(9)
    step = jax.jit(lambda s, b, w: update_step(config, s, b, w))
    ref_step = jax.jit(lambda st, b, w, key: rh.step(
        cfg, rh.EXACT_OPS, st, b, w, key))
    bias0 = [np.asarray(state.critic_params["params"]["torso"][
        f"layer_{i}"]["router"]["bias"]) for i in (1, 2)]
    for t in range(3):
        batch = small_batch(10 + t)
        w = jnp.linspace(0.5, 1.0, B)
        state, m = step(state, batch, w)
        st, rm, key = ref_step(st, (batch.obs, batch.action, batch.reward,
                                    batch.next_obs, batch.discount), w, key)
        assert float(m["critic_loss"]) == pytest.approx(
            float(rm["critic_loss"]), rel=1e-4)
        assert float(m["actor_loss"]) == pytest.approx(
            float(rm["actor_loss"]), rel=1e-4)
        np.testing.assert_allclose(np.asarray(m["td_error"]),
                                   np.asarray(rm["td_error"]), rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(m["route_counts"]),
                                      np.asarray(rm["route_counts"]))
        np.testing.assert_array_equal(np.asarray(m["bias_swapped"]),
                                      np.asarray(rm["bias_swapped"]))
        if t == 0:
            assert tree_gap(state.critic_opt_state[0].mu, st["cm"]) < 2e-3
    for i, b0 in zip((1, 2), bias0):
        got = np.asarray(state.critic_params["params"]["torso"][
            f"layer_{i}"]["router"]["bias"])
        want = np.asarray(st["critic"]["params"]["torso"][f"layer_{i}"][
            "router"]["bias"])
        np.testing.assert_allclose(got, want, atol=1e-7)
        moved = np.round((got - b0) / GAMMA)
        assert np.abs(moved).max() <= 3 and np.abs(moved).sum() > 0
    assert tree_gap(state.critic_params, st["critic"]) < 1e-3
    assert tree_gap(state.target_critic_params, st["t_critic"]) < 1e-5
    assert tree_gap(state.actor_params, st["actor"]) < 1e-3


def test_fused_chunk_reports_both_counters_per_step_and_expert_layer():
    config = small_config()
    state = seeded_state(config, 0)
    cap, k = 64, 2
    trees = dper.init(cap)
    trees = dper.set_leaves_jitted(trees, jnp.arange(cap), jnp.ones((cap,)))
    rows = jax.random.normal(jax.random.key(3), (cap, 32))
    storage = TransitionBatch(
        obs=rows, action=jnp.zeros((cap, 3)), reward=jnp.ones((cap,)),
        next_obs=rows[::-1], done=jnp.zeros((cap,)),
        discount=jnp.full((cap,), 0.99))
    fn = make_fused_chunk(config, k=k, batch_size=B, donate=False)
    _state, _trees, m = fn(state, trees, storage, jnp.int32(cap))
    assert m["route_counts"].shape == (k, 2, 8)
    assert m["bias_swapped"].shape == (k, 2)
    assert m["bias_swapped"].dtype == jnp.int32
    assert np.all(np.asarray(m["route_counts"]).sum(-1) == B * 32 * 2)
    assert np.all(np.isfinite(np.asarray(m["critic_loss"])))


# -- 64-wide heads and the split-K grouped product ---------------------------
def test_64_wide_heads_through_the_kernel_equal_the_blockwise_form():
    """The path the chip takes at LFM2's sizes (8 key/value heads of 64, 4
    queries each), in interpret mode, forward and gradients."""
    assert attn_ops.splash_fits(8192, 64) and attn_ops.splash_fits(4096, 128)
    assert not attn_ops.splash_fits(8192, 48)
    k = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(k[0], (1, 2, 4, 256, 64)) / 8
    kk = jax.random.normal(k[1], (1, 2, 256, 64))
    v = jax.random.normal(k[2], (1, 2, 256, 64))
    cot = jax.random.normal(k[3], (1, 2, 4, 256, 64))

    def run(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * cot), argnums=(0, 1, 2))(
            q, kk, v)

    want = run(lambda q, k, v: attn_ops.blockwise_attention(
        q, k, v, window=None))
    got = run(lambda q, k, v: attn_ops.splash_attention(
        q, k, v, window=None, interpret=True))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-4)


def test_the_grouped_product_with_k_split_over_tiles_matches_ragged_dot(
        monkeypatch):
    """2048 x 1792 is the first product whose tile does not hold K whole."""
    assert grouped_ops._tiling(2048, 1792, grouped_ops.TILE_ELEMS) == (
        256, 1024, 1792)
    assert grouped_ops._tiling(1792, 2048, grouped_ops.TILE_ELEMS) == (
        256, 1792, 1024)
    assert grouped_ops.megablox_fits(2048, 1792)
    # the same split at a size interpret mode affords: K 512 in two tiles
    k = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(k[0], (512, 512))
    w = jax.random.normal(k[1], (2, 512, 128)) / 16
    cot = jax.random.normal(k[2], (512, 128))
    sizes = jnp.asarray([200, 180], jnp.int32)
    valid = (jnp.arange(512) < 380)[:, None]

    def run(impl):
        def loss(x, w):
            y = grouped_ops.grouped_matmul(x, w, sizes, impl=impl,
                                           interpret=True)
            return jnp.sum(jnp.where(valid, y * cot, 0.0))
        return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    want = run("ragged")
    monkeypatch.setattr(grouped_ops, "TILE_ELEMS", 256 * 128)
    monkeypatch.setattr(grouped_ops, "TGMM_TILE_ELEMS", 256 * 128)
    assert grouped_ops._tiling(512, 128, grouped_ops.TILE_ELEMS) == (
        256, 256, 128)
    got = run("megablox")
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-4)
    np.testing.assert_allclose(
        np.asarray(jnp.where(valid, got[1][0], 0.0)),
        np.asarray(jnp.where(valid, want[1][0], 0.0)), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got[1][1]), np.asarray(want[1][1]),
                               rtol=1e-3, atol=1e-3)


# -- the entry point ----------------------------------------------------------
def test_train_main_runs_the_benchmark_files_rehearsal_torso(tmp_path):
    """``--torso`` with the new layer type and router, at the configuration
    file's rehearsal sizes, through ``train.main``: init_state ->
    FusedDeviceReplay -> FusedLoop, finite losses, the chunk still
    ``jit_fn``."""
    from benchmark import cellbuild
    from d4pg_tpu import train
    from d4pg_tpu.obs import trace as program

    cfg = cellbuild.load_config("humanoid-lfm2-ep4", True)
    block = cfg["model"]["torso"]
    assert {"conv", "full_attention"} <= set(block["layer_types"])
    assert block["num_dense_layers"] == 1 and block["use_expert_bias"]
    path = tmp_path / "torso.json"
    path.write_text(json.dumps({"model": {"torso": block}}))
    metrics = train.main([
        "--platform", "cpu", "--env", "point", "--torso", str(path),
        "--p_replay", "1", "--fused_replay", "on", "--replay_storage",
        "device", "--bsize", "2", "--rmsize", "256", "--warmup", "32",
        "--n_eps", "1", "--n_cycles", "2", "--train_steps_per_cycle", "2",
        "--updates_per_dispatch", "1", "--max_steps", "10",
        "--eval_trials", "1", "--log_dir", str(tmp_path / "runs")])
    assert metrics["learner_step"] >= 4
    assert np.isfinite(metrics["critic_loss"])
    assert np.isfinite(metrics["actor_loss"])
    assert metrics["plan"]["fused"] is True and metrics["plan"]["K"] == 1
    # the chunk program is still jit_fn, with the new scopes in its text
    text = program.compiled_text("learner.chunk")
    assert "HloModule jit_fn" in text
    for scope in ("torso.conv", "torso.mlp", "torso.attn_full",
                  "torso.route", "torso.experts"):
        assert scope in text, scope
