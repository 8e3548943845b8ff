"""Nemotron-H's blocks as a torso (``nemotronh``: ``models/torso.py`` over
``ops/ssd.py``) at a small size on the CPU against the plain reference
(``benchmark/reference_ssm.py``, whose recurrence runs token by token): the
pattern string makes the blocks it says, a block has only its own leaves, each
kind of block, the forward pass with its counters, whole gradient steps, the
expert shares adding up to the uncut layer, the fifth older model's tree and
program as the parent's. Sizes: hidden 32, 4 query heads on 2 key/value heads
of 8 without rotary embedding, 4 Mamba heads of 8 in 2 groups with a ``[8, 6]``
state under 4 taps and chunks of 16, 8 relu2 experts top-2 of width 24 under a
sigmoid router with a bias and a scaling factor, an ungated shared expert of
48, 72 tokens (five chunks of the scan, the last short): ``MEM*E``."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_ssm as rs
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.update import update_step
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch

T, D, B = 72, 32, 2
SMALL = dict(
    name="nemotronh", tokens=T, vocab_rows=64, bins=16, hidden_size=D,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    hybrid_override_pattern="MEM*E", mamba_num_heads=4, mamba_head_dim=8,
    ssm_state_size=6, n_groups=2, conv_kernel=4, chunk_size=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    mlp_hidden_act="relu2", shared_expert_intermediate_size=48,
    shared_expert_gated=False, router_scores="sigmoid", use_expert_bias=True,
    routed_scaling_factor=2.5, bias_update_rate=1e-3, norm_topk_prob=True,
    experts_held=[2, 4], rms_norm_eps=1e-5)
MODEL = dict(obs_dim=T, act_dim=3, hidden=(32, 32, 32), n_atoms=11,
             v_min=0.0, v_max=10.0, torso=SMALL)
MAMBA = {"mamba_norm", "in_proj", "conv", "A_log", "dt_bias", "D",
         "out_norm", "out_proj"}
MOE = {"moe_norm", "router", "up", "down", "shared_up", "shared_down"}
ATTENTION = {"attn_norm", "q", "k", "v", "o"}


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
    """``init_state`` with the gains, the skips and the routing biases moved
    off their initial values, so that a test sees them."""
    state = init_state(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 1000))

    def move(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        if names[-1] == "scale" or names[-2] == "D":
            return x + 0.3 * jax.random.normal(next(keys), x.shape)
        if names[-2:] == ["router", "bias"]:
            return 0.05 * jax.random.normal(next(keys), x.shape)
        return x

    critic = jax.tree_util.tree_map_with_path(move, state.critic_params)
    return state._replace(
        critic_params=critic,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic))


def tree_gap(a, b):
    diff = jax.tree_util.tree_map(lambda x, y: x - y, a, b)
    return float(np.max(reference.leaf_norms(diff)
                        / np.maximum(reference.leaf_norms(b), 1e-12)))


# -- the seam -----------------------------------------------------------------
def test_the_pattern_string_makes_the_blocks_it_says():
    spec = small_config().torso
    assert spec.layer_types == ("mamba", "moe", "mamba", "attention", "moe")
    assert spec.expert_layers == (1, 4)
    assert spec.rope_for("attention") is None and spec.rope_parameters is None
    assert torso_lib.pattern_blocks("MEMEM*E") == (
        "mamba", "moe", "mamba", "moe", "mamba", "attention", "moe")
    assert hash(small_config()) == hash(small_config())
    assert type(small_config().build_critic().torso) \
        is torso_lib.TORSOS["mellum2"]
    # the same blocks written out are the same torso
    assert small_config(layer_types=list(spec.layer_types)).torso == spec
    with pytest.raises(ValueError, match="unknown blocks"):
        small_config(hybrid_override_pattern="ME-M")
    with pytest.raises(ValueError, match="are not hybrid_override_pattern"):
        small_config(layer_types=["mamba", "moe"])
    with pytest.raises(ValueError, match="mamba blocks need"):
        small_config(ssm_state_size=0)
    with pytest.raises(ValueError, match="n_groups"):
        small_config(n_groups=3)
    with pytest.raises(ValueError, match="mlp_hidden_act"):
        small_config(mlp_hidden_act="gelu")
    with pytest.raises(ValueError, match="without leading dense"):
        small_config(num_dense_layers=1, intermediate_size=16)
    # a layer of two branches still needs its rope block
    with pytest.raises(ValueError, match="rope_parameters has no block"):
        small_config(hybrid_override_pattern="",
                     layer_types=["full_attention"])


def test_a_block_has_only_its_own_leaves():
    layers = init_state(small_config(), jax.random.key(0)).critic_params[
        "params"]["torso"]
    assert set(layers) == {"embed", "final_norm", *(
        f"layer_{i}" for i in range(5))}
    for i, want in enumerate((MAMBA, MOE, MAMBA, ATTENTION, MOE)):
        assert set(layers[f"layer_{i}"]) == want, i
    mam, moe, att = layers["layer_0"], layers["layer_1"], layers["layer_3"]
    # [z | xBC | dt]: 32 | 32 + 2 x 2 x 6 | 4
    assert mam["in_proj"]["kernel"].shape == (D, 32 + 56 + 4)
    assert mam["conv"]["kernel"].shape == (56, 4)
    assert mam["conv"]["bias"].shape == (56,)
    assert mam["A_log"]["value"].shape == mam["dt_bias"]["value"].shape \
        == mam["D"]["value"].shape == (4,)
    assert mam["out_norm"]["scale"].shape == (32,)
    assert mam["out_proj"]["kernel"].shape == (32, D)
    # two matrices an expert, no gate; the shared expert ungated
    assert moe["up"]["kernel"].shape == (2, D, 24)
    assert moe["down"]["kernel"].shape == (2, 24, D)
    assert moe["shared_up"]["kernel"].shape == (D, 48)
    assert set(moe["router"]) == {"kernel", "bias"}
    assert att["q"]["kernel"].shape == (D, 32)
    assert att["k"]["kernel"].shape == (D, 16)
    # Mamba-2's seeding: A in [1, 16), dt in [1e-3, 1e-1], D ones, the taps
    # at their own fan-in with a bias inside 1 / sqrt(taps)
    a = np.exp(np.asarray(mam["A_log"]["value"]))
    dt = np.log1p(np.exp(np.asarray(mam["dt_bias"]["value"])))
    assert np.all((a >= 1) & (a < 16)) and np.all((dt >= 1e-3) & (dt <= 0.1))
    np.testing.assert_array_equal(np.asarray(mam["D"]["value"]), 1.0)
    bias = np.asarray(mam["conv"]["bias"])
    assert np.abs(bias).max() <= 0.5 and np.abs(bias).max() > 0.3
    assert not np.array_equal(a, np.exp(np.asarray(
        layers["layer_2"]["A_log"]["value"])))  # a block's draw is its own


def test_the_fifth_older_models_tree_and_program_are_the_parents():
    """``tests/test_torso_loop.py`` pins the four models before it; this is
    Ouro's digest by the same recipe on the parent commit (1190fdf), with
    every pass's latent and the gate in the differentiated sum."""
    from benchmark import cellbuild

    block = cellbuild.load_config("humanoid-ouro-ut4", True)["model"]["torso"]
    torso = torso_lib.build_torso(torso_lib.TorsoSpec.from_dict(block))
    params = torso.init(jax.random.key(7))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    obs = jax.ShapeDtypeStruct((2, block["tokens"]), jnp.float32)

    def loss(p, o):
        z, aux = torso.apply(p, o, train=True)
        z = z + jnp.sum(aux["pass_latents"], 0) * jnp.sum(aux["exit_logits"])
        return jnp.sum(z)

    text = jax.jit(lambda p, o: jax.value_and_grad(
        lambda p: loss(p, o))(p)).lower(params, obs).as_text()
    assert (h.hexdigest()[:16],
            hashlib.sha256(text.encode()).hexdigest()[:16]) == (
        "8a3e5787d46c6a7b", "296fdeda85e3eaf8")


# -- each kind of block and the whole step against the reference --------------
@pytest.mark.parametrize("index, kind", [(0, "mamba"), (1, "moe"),
                                         (3, "attention")])
def test_each_kind_of_block_matches_the_reference(index, kind):
    config = small_config()
    torso = config.build_critic().torso
    p = seeded_state(config, 5).critic_params["params"]["torso"][
        f"layer_{index}"]
    x = jax.random.normal(jax.random.key(index), (T, D))
    got, stats, sel = torso._sequence(p, x, kind, False, True)
    want, ref_stats = rs.block(rs.EXACT_OPS, SMALL, p, x, kind)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    assert sel == ()
    if kind == "mamba":
        assert set(stats) == {"ssd_kept"}
        assert float(stats["ssd_kept"]) == pytest.approx(
            float(ref_stats[0]), rel=1e-5)
        assert 0.5 < float(stats["ssd_kept"]) < 1.0
        # a state set to zero at every 16th token is another block: memory
        # across a chunk's edge reaches the output
        reset, _ = rs.block(rs.EXACT_OPS, SMALL, p, x, kind, reset_every=16)
        np.testing.assert_allclose(np.asarray(reset[:16]),
                                   np.asarray(want[:16]), rtol=1e-4,
                                   atol=1e-5)
        assert np.abs(np.asarray(reset[16:] - want[16:])).max() > 1e-2
        # the taps' bias and the skip reach the output
        for name, leaf in (("conv", "bias"), ("D", "value")):
            off = {**p, name: {**p[name], leaf: jnp.zeros_like(
                p[name][leaf])}}
            assert np.abs(np.asarray(
                torso._mamba(off, x)[0] - got)).max() > 1e-3, name
    elif kind == "moe":
        assert set(stats) == {"route_counts", "bias_swapped"}
        np.testing.assert_array_equal(np.asarray(stats["route_counts"]),
                                      np.asarray(ref_stats[0]))
        assert int(stats["bias_swapped"]) == int(ref_stats[1]) > 0
        assert int(np.asarray(stats["route_counts"]).sum()) == 2 * T
    else:
        assert stats == {} and ref_stats == ()
        # no rotary embedding: q and k are the projections themselves
        h = jax.random.normal(jax.random.key(9), (T, D))
        q, k, _v, gate = torso._qkv(p, h, kind)
        assert gate is None
        np.testing.assert_allclose(
            np.asarray(k).transpose(1, 0, 2).reshape(T, -1),
            np.asarray(jnp.dot(h, p["k"]["kernel"], precision="highest")),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(q).transpose(2, 0, 1, 3).reshape(T, -1) * 8 ** 0.5,
            np.asarray(jnp.dot(h, p["q"]["kernel"], precision="highest")),
            rtol=1e-5, atol=1e-5)


def test_forward_pass_and_counters_match_the_reference():
    config = small_config()
    state = seeded_state(config, 2)
    batch = small_batch()
    latent, aux = config.build_critic().latent(state.critic_params,
                                               batch.obs, train=True)
    z, counts, swapped, kept = rs.torso(
        rs.EXACT_OPS, SMALL, state.critic_params["params"]["torso"],
        batch.obs)
    np.testing.assert_allclose(np.asarray(latent), np.asarray(z), rtol=2e-4,
                               atol=2e-5)
    # a row an E block, a row an M block, nothing of the attention block
    assert set(aux) == {"route_counts", "bias_swapped", "ssd_kept"}
    assert aux["route_counts"].shape == (2, 8)
    assert aux["bias_swapped"].shape == aux["ssd_kept"].shape == (2,)
    assert aux["ssd_kept"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(aux["route_counts"]),
                                  np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(aux["bias_swapped"]),
                                  np.asarray(swapped))
    np.testing.assert_allclose(np.asarray(aux["ssd_kept"]),
                               np.asarray(kept), rtol=1e-5)
    assert int(np.asarray(counts).sum()) == 2 * B * T * 2


def test_whole_steps_match_the_reference():
    """Two steps: losses, TD errors, counters, the gradient (Adam's first
    moment after one step is 0.1 of it; every new leaf has one), the
    parameters and the biases the rule moved."""
    config = small_config()
    state = seeded_state(config, 1)
    cfg = reference.model_cfg({**MODEL, "torso": SMALL})
    st = rs.init(state.actor_params, state.critic_params)
    step = jax.jit(lambda s, b, w: update_step(config, s, b, w))

    @jax.jit
    def ref_step(st, batch, w):
        proj = rs.target(cfg, rs.EXACT_OPS, st, batch)
        grads, m = rs.critic_grads(cfg, rs.EXACT_OPS, st["critic"], batch, w,
                                   proj)
        new, m["actor_loss"] = rs.actor_update(
            cfg, rs.EXACT_OPS, rs.critic_adam(cfg, st, grads,
                                              m["route_counts"]),
            st["count"], batch)
        return new, m

    bias0 = np.asarray(state.critic_params["params"]["torso"]["layer_1"][
        "router"]["bias"])
    for t in range(2):
        batch = small_batch(10 + t)
        w = jnp.linspace(0.5, 1.0, B)
        state, m = step(state, batch, w)
        st, rm = ref_step(st, (batch.obs, batch.action, batch.reward,
                               batch.next_obs, batch.discount), w)
        assert float(m["critic_loss"]) == pytest.approx(
            float(rm["critic_loss"]), rel=1e-4)
        assert float(m["actor_loss"]) == pytest.approx(
            float(rm["actor_loss"]), rel=1e-4)
        np.testing.assert_allclose(np.asarray(m["td_error"]),
                                   np.asarray(rm["td_error"]), rtol=1e-4)
        for name in ("route_counts", "bias_swapped"):
            np.testing.assert_array_equal(np.asarray(m[name]),
                                          np.asarray(rm[name]))
        np.testing.assert_allclose(np.asarray(m["ssd_kept"]),
                                   np.asarray(rm["ssd_kept"]), rtol=1e-5)
        if t == 0:
            mu = state.critic_opt_state[0].mu
            assert tree_gap(mu, st["cm"]) < 5e-3
            mam = mu["params"]["torso"]["layer_0"]
            for leaf in MAMBA:
                assert all(float(jnp.max(jnp.abs(x))) > 0 for x in
                           jax.tree_util.tree_leaves(mam[leaf])), leaf
    assert tree_gap(state.critic_params, st["critic"]) < 1e-3
    assert tree_gap(state.target_critic_params, st["t_critic"]) < 1e-5
    assert tree_gap(state.actor_params, st["actor"]) < 1e-3
    moved = np.asarray(state.critic_params["params"]["torso"]["layer_1"][
        "router"]["bias"]) - bias0
    assert set(np.round(np.abs(moved) / 1e-3).tolist()) <= {0.0, 2.0}
    assert np.abs(moved).max() > 1e-3  # two steps the same way somewhere


def test_the_shares_of_an_expert_block_add_up_to_the_uncut_layer():
    """The parts of an ``E`` block that every share of two experts gives,
    the shared expert (which every chip computes alike) counted once, add up
    to what the uncut reference gives for the whole layer."""
    config = small_config()
    p = seeded_state(config, 3).critic_params["params"]["torso"]["layer_1"]
    k = jax.random.split(jax.random.key(4), 3)
    full = {**p, "up": {"kernel": jax.random.normal(k[0], (8, D, 24))
                        / D ** 0.5},
            "down": {"kernel": jax.random.normal(k[1], (8, 24, D))
                     / 24 ** 0.5}}
    h = jax.random.normal(k[2], (T, D))
    whole, counts, _sw = rs.moe_op(rs.EXACT_OPS, SMALL, full, h, held=(0, 8))
    shared = rs.relu2(rs.EXACT_OPS, h, p["shared_up"]["kernel"],
                      p["shared_down"]["kernel"])
    total = jnp.zeros_like(h)
    for lo in range(0, 8, 2):
        spec = torso_lib.TorsoSpec.from_dict({**SMALL,
                                              "experts_held": [lo, lo + 2]})
        part = {**p, "up": {"kernel": full["up"]["kernel"][lo:lo + 2]},
                "down": {"kernel": full["down"]["kernel"][lo:lo + 2]}}
        out, stats = torso_lib.expert_share(spec, part, h, jnp.float32)
        np.testing.assert_array_equal(np.asarray(stats["route_counts"]),
                                      np.asarray(counts))
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)
    # the routed weights carry the scaling factor: a token's sum to 2.5
    w, _e, _c, _s = rs.route(SMALL, h, p["router"])
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 2.5, rtol=1e-5)
    assert np.abs(np.asarray(whole - shared)).max() > 1e-2


def test_fused_chunk_reports_the_new_counters_per_step_and_block():
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
    assert m["route_counts"].shape == (k, 2, 8)
    assert m["bias_swapped"].shape == m["ssd_kept"].shape == (k, 2)
    kept = np.asarray(m["ssd_kept"])
    assert kept.dtype == np.float32 and np.all((kept > 0.5) & (kept < 1.0))
    assert np.all(np.asarray(m["route_counts"]).sum(-1) == B * T * 2)
    assert np.all(np.isfinite(np.asarray(m["critic_loss"])))


def test_train_main_runs_the_benchmark_files_rehearsal_torso(tmp_path):
    """``--torso`` with the pattern string and the new keys, at the
    configuration file's rehearsal sizes, through ``train.main``: init_state
    -> FusedDeviceReplay -> FusedLoop, finite losses, the chunk still
    ``jit_fn`` with the new scopes in it."""
    import json

    from benchmark import cellbuild
    from d4pg_tpu import train
    from d4pg_tpu.obs import trace as program

    cfg = cellbuild.load_config("humanoid-nemotronh-ep16", True)
    block = cfg["model"]["torso"]
    assert set(block["hybrid_override_pattern"]) == {"M", "E", "*"}
    assert "layer_types" not in block and "rope_parameters" not in block
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
    text = program.compiled_text("learner.chunk")
    assert "HloModule jit_fn" in text
    for scope in ("torso.mamba", "torso.ssd_scan", "torso.attn_full",
                  "torso.shared_expert", "torso.route", "torso.experts"):
        assert scope in text, scope
    assert "torso.mlp" not in text and "torso.conv" not in text
