"""Qwen3-Next's layers as a torso (``qwen3next``: ``models/torso.py`` over
``ops/delta_rule.py``) at a small size on the CPU against the plain reference
(``benchmark/reference_linear.py``, whose recurrence runs token by token):
each kind of layer, the forward pass with its counters, whole gradient steps;
the depthwise convolution's first positions; the partial rotation; the gated
attention output; the shared expert and its gate; 256-wide heads through the
kernel path the chip takes; the seeded trees of the three older models
bit-equal to the parent's; the normal path through ``train.main``. Sizes:
hidden 64, 4 query heads on 2 key/value heads of 16 (a quarter rotated), 2 key
and 4 value heads of 8 under 4 taps, 16 experts top-3 of width 32 with a
shared expert of 32, 200 tokens (four chunks of the scan, the last short):
``linear_attention`` x 2, ``full_attention``."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_linear as rl
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.update import update_step
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.ops import attention as attn_ops
from d4pg_tpu.ops import short_conv
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROPE = {"full_attention": {"rope_type": "default", "rope_theta": 10000000}}
T = 200
SMALL = dict(
    name="qwen3next", tokens=T, vocab_rows=64, bins=16, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, qk_norm=True,
    attn_output_gate=True, partial_rotary_factor=0.25,
    layer_types=["linear_attention", "linear_attention", "full_attention"],
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_experts=16, num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, experts_held=[4, 8],
    rms_norm_eps=1e-6, rope_parameters=ROPE)
MODEL = dict(obs_dim=T, act_dim=3, hidden=(32, 32, 32), n_atoms=11,
             v_min=0.0, v_max=10.0, torso=SMALL)
B = 2


def small_config(**torso_over):
    return D4PGConfig(**{**MODEL, "torso": {**SMALL, **torso_over}})


def small_batch(seed=1):
    k = jax.random.split(jax.random.key(seed), 4)
    return TransitionBatch(
        obs=3.0 * jax.random.normal(k[0], (B, T)),
        action=jax.random.uniform(k[1], (B, 3), minval=-1, maxval=1),
        reward=jax.random.normal(k[2], (B,)),
        next_obs=jax.random.normal(k[3], (B, T)),
        done=jnp.zeros((B,)), discount=jnp.full((B,), 0.99))


def seeded_state(config, seed=0):
    """``init_state`` with the norms' gains moved off 1, so that a test sees
    them (``A_log`` and ``dt_bias`` are seeded apart by ``init`` itself)."""
    state = init_state(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 1000))

    def move(path, x):
        if str(getattr(path[-1], "key", path[-1])) == "scale":
            return x + 0.3 * jax.random.normal(next(keys), x.shape)
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
def test_spec_takes_the_new_layer_type_and_keys_as_data():
    config = small_config()
    spec = config.torso
    assert spec.rotary_dim == 4 and spec.attn_output_gate
    assert spec.expert_layers == (0, 1, 2)
    assert hash(config) == hash(small_config())
    assert type(config.build_critic().torso) is torso_lib.TORSOS["mellum2"]
    assert "linear_attention" in torso_lib.LAYER_TYPES
    layers = init_state(config, jax.random.key(0)).critic_params[
        "params"]["torso"]
    experts = {"moe_norm", "router", "gate", "up", "down", "shared_gate",
               "shared_up", "shared_down", "shared_expert_gate"}
    # a layer has only the leaves it has
    assert set(layers["layer_0"]) == experts | {
        "linear_norm", "in_proj_qkvz", "in_proj_ba", "conv", "A_log",
        "dt_bias", "out_norm", "out_proj"}
    assert set(layers["layer_2"]) == experts | {
        "attn_norm", "q", "k", "v", "o", "q_norm", "k_norm"}
    lin, att = layers["layer_0"], layers["layer_2"]
    # [q, k, v, z]: 2 key heads and 4 value heads of 8
    assert lin["in_proj_qkvz"]["kernel"].shape == (64, 16 + 16 + 32 + 32)
    assert lin["in_proj_ba"]["kernel"].shape == (64, 8)
    assert lin["conv"]["kernel"].shape == (64, 4)  # q, k, v channels, taps
    assert lin["A_log"]["value"].shape == lin["dt_bias"]["value"].shape \
        == (4,)
    assert lin["out_norm"]["scale"].shape == (8,)
    assert lin["out_proj"]["kernel"].shape == (32, 64)
    assert att["q"]["kernel"].shape == (64, 2 * 64)  # a query and its gate
    assert att["o"]["kernel"].shape == (64, 64)
    assert att["shared_expert_gate"]["kernel"].shape == (64, 1)
    assert att["shared_down"]["kernel"].shape == (32, 64)
    assert att["gate"]["kernel"].shape == (4, 64, 32)
    # the taps at their own fan-in, 4; A in (0, 16), dt in [1e-3, 1e-1]
    assert float(jnp.std(lin["conv"]["kernel"])) == pytest.approx(
        0.5, rel=0.2)
    a = np.exp(np.asarray(lin["A_log"]["value"]))
    dt = np.log1p(np.exp(np.asarray(lin["dt_bias"]["value"])))
    assert np.all((a > 0) & (a < 16)) and np.all((dt >= 1e-3) & (dt <= 0.1))
    assert not np.array_equal(a, np.exp(np.asarray(
        layers["layer_1"]["A_log"]["value"])))  # a layer's draw is its own
    with pytest.raises(ValueError, match="linear_"):
        small_config(linear_conv_kernel_dim=0)
    with pytest.raises(ValueError, match="value heads"):
        small_config(linear_num_value_heads=3)
    with pytest.raises(ValueError, match="partial_rotary_factor"):
        small_config(partial_rotary_factor=0.3)
    with pytest.raises(ValueError, match="unknown layer types"):
        small_config(layer_types=["recurrent_attention"])


@pytest.mark.parametrize("which, want", [
    ("mellum2.init", "67642a3108d252ba"), ("keye2.init", "1a3dfac60acd2b57"),
    ("lfm2.init", "2ce7c2785c5e30a5"),
    ("humanoid-mellum2-ep4.seeded", "85ec67476d7952e3"),
    ("humanoid-keye2-ep8.seeded", "42f46311268478e9"),
    ("humanoid-lfm2-ep4.seeded", "1b3940467dae7da8")])
def test_the_three_older_models_seeded_trees_are_the_parents_bit_for_bit(
        which, want):
    """Digests taken on the parent commit (2c25f32): ``init_state`` of the
    three older models' test configurations and the benchmark's seeded
    weights at their rehearsal sizes."""
    name, kind = which.split(".")
    if kind == "init":
        import importlib
        import sys

        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        mod = importlib.import_module({
            "mellum2": "test_torso", "keye2": "test_torso_sparse",
            "lfm2": "test_torso_hybrid"}[name])
        tree = init_state(mod.small_config(),
                          jax.random.key(0)).critic_params
    else:
        from benchmark import cellbuild
        from benchmark.drivers import learner_static_hybrid as hybrid
        from benchmark.drivers import learner_static_torso as drv

        cfg = cellbuild.load_config(name, True)
        config = cellbuild.learner_config(cfg)
        seeded = (lambda s: hybrid.seeded_params(cfg, config, s)) \
            if "lfm2" in name else (lambda s: drv.seeded_params(config, s))
        tree = jax.jit(seeded)(jnp.uint32(12345))[1]
    assert digest(tree) == want


# -- the convolution, the rotation, the gates ---------------------------------
def test_the_first_three_positions_of_the_convolution_read_zeros():
    k = jax.random.split(jax.random.key(0), 2)
    g = jax.random.normal(k[0], (9, 5))
    taps = jax.random.normal(k[1], (5, 4))
    got = np.asarray(short_conv.depthwise_causal(g, taps))
    x, w = np.asarray(g, np.float64), np.asarray(taps, np.float64)
    # the last tap reads the position itself, the first three positions back
    np.testing.assert_allclose(got[0], w[:, 3] * x[0], rtol=1e-5)
    np.testing.assert_allclose(got[1], w[:, 3] * x[1] + w[:, 2] * x[0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        got[2], w[:, 3] * x[2] + w[:, 2] * x[1] + w[:, 1] * x[0], rtol=1e-5,
        atol=1e-6)
    want = np.zeros((9, 5))
    for t in range(9):
        for j in range(4):
            if t - (3 - j) >= 0:
                want[t] += w[:, j] * x[t - (3 - j)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the reference's explicit sum on a padded array is the same sum
    np.testing.assert_allclose(
        np.asarray(rl.rh.short_conv(g, taps)), want, rtol=1e-5, atol=1e-6)
    # causal: a later position moves nothing before it
    moved = np.asarray(short_conv.depthwise_causal(g.at[4].add(1.0), taps))
    changed = np.any(moved != got, axis=1)
    assert not changed[:4].any() and changed[4:8].all() \
        and not changed[8:].any()


def test_the_partial_rotation_leaves_the_rest_of_a_head_as_it_was():
    """RoPE turns the first ``rotary_dim`` = 4 of a head's 16 (by halves:
    element ``i`` with ``i + 2``); the other 12 are the normed projections
    whatever the position and whatever ``rope_theta``."""
    config = small_config()
    torso = config.build_critic().torso
    p = seeded_state(config, 4).critic_params["params"]["torso"]["layer_2"]
    h = jax.random.normal(jax.random.key(0), (T, 64))
    q, k, v, gate = torso._qkv(p, h, "full_attention")
    assert q.shape == (2, 2, T, 16) and k.shape == v.shape == (2, T, 16)
    assert gate.shape == (T, 64) and gate.dtype == jnp.float32
    other = small_config(rope_parameters={"full_attention": {
        "rope_type": "default", "rope_theta": 100.0}}).build_critic().torso
    q2, k2, _v, _g = other._qkv(p, h, "full_attention")
    np.testing.assert_array_equal(np.asarray(q[..., 4:]),
                                  np.asarray(q2[..., 4:]))
    np.testing.assert_array_equal(np.asarray(k[..., 4:]),
                                  np.asarray(k2[..., 4:]))
    assert np.abs(np.asarray(q[..., 1:, :4] - q2[..., 1:, :4])).max() > 1e-3
    # position 0 is turned by nothing
    np.testing.assert_array_equal(np.asarray(q[..., 0, :]),
                                  np.asarray(q2[..., 0, :]))
    # against the rotation written out: the passed part is the normed
    # projection itself, the turned part pairs element i with i + 2
    proj = np.asarray(jnp.dot(h, p["k"]["kernel"], precision="highest"),
                      np.float64).reshape(T, 2, 16)
    normed = proj / np.sqrt(np.mean(proj ** 2, -1, keepdims=True) + 1e-6) \
        * np.asarray(p["k_norm"]["scale"], np.float64)
    np.testing.assert_allclose(np.asarray(k).transpose(1, 0, 2)[..., 4:],
                               normed[..., 4:], rtol=1e-4, atol=1e-5)
    inv = 1e7 ** (-np.arange(0, 4, 2) / 4)
    ang = np.arange(T)[:, None] * inv[None, :]  # [T, 2]
    x1, x2 = normed[..., :2], normed[..., 2:4]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    turned = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(np.asarray(k).transpose(1, 0, 2)[..., :4],
                               turned, rtol=1e-3, atol=1e-4)


def test_the_shared_expert_is_added_whole_under_its_gate():
    config = small_config()
    spec = config.torso
    p = seeded_state(config, 6).critic_params["params"]["torso"]["layer_0"]
    h = jax.random.normal(jax.random.key(2), (T, 64))
    out, stats = torso_lib.expert_share(spec, p, h, jnp.float32)
    bare = dict(spec.__dict__, shared_expert_intermediate_size=0)
    routed, bare_stats = torso_lib.expert_share(
        torso_lib.TorsoSpec(**bare), p, h, jnp.float32)
    assert "shared_gate" not in bare_stats
    alike, gate_mean = rl.shared_expert(rl.EXACT_OPS, p, h)
    np.testing.assert_allclose(np.asarray(out - routed), np.asarray(alike),
                               rtol=1e-4, atol=1e-5)
    assert float(stats["shared_gate"]) / T == pytest.approx(
        float(gate_mean), rel=1e-5)
    assert 0.2 < float(gate_mean) < 0.8
    # the gate is one number a token: closing it leaves the routed part
    shut = {**p, "shared_expert_gate": {"kernel": jnp.zeros((64, 1))}}
    half, _ = torso_lib.expert_share(spec, shut, h, jnp.float32)
    ungated = np.asarray(alike) / np.asarray(jax.nn.sigmoid(jnp.dot(
        h, p["shared_expert_gate"]["kernel"], precision="highest")))
    np.testing.assert_allclose(np.asarray(half - routed), 0.5 * ungated,
                               rtol=1e-3, atol=1e-4)


# -- each kind of layer and the whole step against the reference --------------
@pytest.mark.parametrize("index, layer_type", [
    (0, "linear_attention"), (2, "full_attention")])
def test_each_kind_of_layer_matches_the_reference(index, layer_type):
    config = small_config()
    torso = config.build_critic().torso
    p = seeded_state(config, 5).critic_params["params"]["torso"][
        f"layer_{index}"]
    x = jax.random.normal(jax.random.key(index), (T, 64))
    got, stats, _sel = torso._sequence(p, x, layer_type, False, True)
    want, (counts, kept, shared) = rl.layer(rl.EXACT_OPS, SMALL, p, x,
                                            layer_type)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(stats["route_counts"]),
                                  np.asarray(counts))
    assert float(stats["shared_gate"]) / T == pytest.approx(float(shared),
                                                            rel=1e-5)
    assert ("delta_kept" in stats) == (layer_type == "linear_attention")
    if "delta_kept" in stats:
        assert float(stats["delta_kept"]) == pytest.approx(float(kept),
                                                           rel=1e-5)
        assert 0.5 < float(kept) < 1.0
        # a state set to zero at every 64th token is another layer: memory
        # across a chunk's edge reaches the output
        reset, _ = rl.layer(rl.EXACT_OPS, SMALL, p, x, layer_type,
                            reset_every=64)
        np.testing.assert_allclose(np.asarray(reset[:64]),
                                   np.asarray(want[:64]), rtol=1e-4,
                                   atol=1e-5)
        assert np.abs(np.asarray(reset[64:] - want[64:])).max() > 1e-2
    else:
        # the gate: with a head's gate logits at zero every output is half
        # the ungated attention, in the program and in the reference alike,
        # and that is not what the seeded gate gives
        zero = {**p, "q": {"kernel": p["q"]["kernel"].reshape(
            64, 4, 2, 16).at[:, :, 1].set(0.0).reshape(64, 128)}}
        a = torso._attend(zero, x, layer_type) - x
        b = rl.attention_op(rl.EXACT_OPS, SMALL, zero, rl.rt.rms(
            x, p["attn_norm"]["scale"], 1e-6))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)
        seeded = torso._attend(p, x, layer_type) - x
        assert np.abs(np.asarray(seeded - a)).max() > 1e-2


def test_forward_pass_and_counters_match_the_reference():
    config = small_config()
    state = seeded_state(config, 2)
    batch = small_batch()
    latent, aux = config.build_critic().latent(state.critic_params,
                                               batch.obs, train=True)
    z, counts, kept, shared = rl.torso(
        rl.EXACT_OPS, SMALL, state.critic_params["params"]["torso"],
        batch.obs)
    np.testing.assert_allclose(np.asarray(latent), np.asarray(z), rtol=2e-4,
                               atol=2e-5)
    assert aux["route_counts"].shape == (3, 16)
    assert aux["delta_kept"].shape == (2,)  # the DeltaNet layers alone
    assert aux["shared_gate"].shape == (3,)
    assert aux["delta_kept"].dtype == aux["shared_gate"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(aux["route_counts"]),
                                  np.asarray(counts))
    np.testing.assert_allclose(np.asarray(aux["delta_kept"]),
                               np.asarray(kept), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(aux["shared_gate"]),
                               np.asarray(shared), rtol=1e-5)
    assert int(np.asarray(counts).sum()) == 3 * B * T * 3
    # the untrained pass hands the same latent back
    plain, _aux = config.build_critic().latent(state.critic_params, batch.obs)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(latent),
                               rtol=1e-6, atol=1e-6)


def test_whole_steps_match_the_reference():
    """Two steps: losses, TD errors, counters, the gradient (Adam's first
    moment after one step is 0.1 of it; every new leaf has one), the
    parameters."""
    config = small_config()
    state = seeded_state(config, 1)
    cfg = reference.model_cfg({**MODEL, "torso": SMALL})
    st = rl.init(state.actor_params, state.critic_params)
    key = jax.random.key(9)
    step = jax.jit(lambda s, b, w: update_step(config, s, b, w))
    ref_step = jax.jit(lambda st, b, w, key: rl.step(
        cfg, rl.EXACT_OPS, st, b, w, key))
    for t in range(2):
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
        np.testing.assert_allclose(np.asarray(m["delta_kept"]),
                                   np.asarray(rm["delta_kept"]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(m["shared_gate"]),
                                   np.asarray(rm["shared_gate"]), rtol=1e-5)
        if t == 0:
            mu = state.critic_opt_state[0].mu
            assert tree_gap(mu, st["cm"]) < 5e-3
            lin = mu["params"]["torso"]["layer_0"]
            for leaf in ("A_log", "dt_bias", "conv", "in_proj_ba",
                         "out_norm", "shared_expert_gate"):
                assert float(jnp.max(jnp.abs(
                    jax.tree_util.tree_leaves(lin[leaf])[0]))) > 0, leaf
    assert tree_gap(state.critic_params, st["critic"]) < 1e-3
    assert tree_gap(state.target_critic_params, st["t_critic"]) < 1e-5
    assert tree_gap(state.actor_params, st["actor"]) < 1e-3


def test_fused_chunk_reports_the_new_counters_per_step_and_layer():
    config = small_config()
    state = seeded_state(config, 0)
    cap, k = 64, 2
    trees = dper.init(cap)
    trees = dper.set_leaves_jitted(trees, jnp.arange(cap), jnp.ones((cap,)))
    rows = jax.random.normal(jax.random.key(3), (cap, T))
    storage = TransitionBatch(
        obs=rows, action=jnp.zeros((cap, 3)), reward=jnp.ones((cap,)),
        next_obs=rows[::-1], done=jnp.zeros((cap,)),
        discount=jnp.full((cap,), 0.99))
    fn = make_fused_chunk(config, k=k, batch_size=B, donate=False)
    _state, _trees, m = fn(state, trees, storage, jnp.int32(cap))
    assert m["route_counts"].shape == (k, 3, 16)
    assert m["delta_kept"].shape == (k, 2)
    assert m["shared_gate"].shape == (k, 3)
    assert m["delta_kept"].dtype == jnp.float32
    kept = np.asarray(m["delta_kept"])
    assert np.all((kept > 0.5) & (kept < 1.0))
    assert np.all(np.asarray(m["route_counts"]).sum(-1) == B * T * 3)
    assert np.all(np.isfinite(np.asarray(m["critic_loss"])))


# -- 256-wide heads through the kernel ----------------------------------------
def test_256_wide_heads_through_the_kernel_equal_the_blockwise_form():
    """The path the chip takes at Qwen3-Next's sizes (2 key/value heads of
    256, 8 queries each), in interpret mode, forward and gradients."""
    assert attn_ops.splash_fits(16384, 256)
    k = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(k[0], (1, 2, 8, 128, 256)) / 16
    kk = jax.random.normal(k[1], (1, 2, 128, 256))
    v = jax.random.normal(k[2], (1, 2, 128, 256))
    cot = jax.random.normal(k[3], (1, 2, 8, 128, 256))

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


# -- the entry point ----------------------------------------------------------
def test_train_main_runs_the_benchmark_files_rehearsal_torso(tmp_path):
    """``--torso`` with the new layer type and keys, at the configuration
    file's rehearsal sizes, through ``train.main``: init_state ->
    FusedDeviceReplay -> FusedLoop, finite losses, the chunk still
    ``jit_fn``."""
    from benchmark import cellbuild
    from d4pg_tpu import train
    from d4pg_tpu.obs import trace as program

    cfg = cellbuild.load_config("humanoid-qwen3next-ep32", True)
    block = cfg["model"]["torso"]
    assert {"linear_attention", "full_attention"} == set(block["layer_types"])
    assert block["tokens"] >= 3 * 64 and block["attn_output_gate"]
    lo, hi = block["experts_held"]
    assert 0 < hi - lo < block["num_experts"]
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
    for scope in ("torso.deltanet", "torso.delta_scan", "torso.attn_full",
                  "torso.shared_expert", "torso.route", "torso.experts"):
        assert scope in text, scope
