"""Trinity-Mini's layers as a torso (``trinity``: ``models/torso.py``'s one
layer path with its flags together) at a small size on the CPU against the
plain reference (``benchmark/reference_mix.py``): the seam (an explicit
``null`` rope block, the embedding's multiplier), each kind of layer, the
forward pass with its counters, a window that cuts and the two rotary regimes
each shown to matter, whole gradient steps with the bias rule, the sixteen
expert shares adding up to the uncut layer, the sixth older model's tree and
program as the parent's. Sizes: hidden 32, 4 query heads on 2 key/value heads
of 8 with a gate and q/k norms, window 24 of 80 tokens, a dense layer of 48,
16 SwiGLU experts top-2 of width 24 under a sigmoid router with a bias and
the published 2.826, an ungated shared expert of 24: ``[sliding dense, sliding
moe, full moe]``.

Tolerances, each with its reason: the program in float32 against the
reference in float32 at ``HIGHEST`` differ by the order of their sums alone
(blocks of queries and keys here, one softmax over all keys there; a sorted
buffer here, a dense mask there): 2e-4 relative on activations of order 1
after three layers, 1e-4 on losses and TD errors, 5e-3 on Adam's first moment
by the worst leaf (a gradient leaf of norm ~1e-3 beside one of ~1), 1e-3 on
the parameters after two steps. Counters are integers and compare exactly.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_mix as rm
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.update import update_step
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.replay.uniform import TransitionBatch

T, D, B = 80, 32, 2
ROPE = {"sliding_attention": {"rope_type": "default", "rope_theta": 10000},
        "full_attention": None}
SMALL = dict(
    name="trinity", tokens=T, vocab_rows=64, bins=16, hidden_size=D,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_types=["sliding_attention", "sliding_attention", "full_attention"],
    sliding_window=24, qk_norm=True, attn_output_gate=True,
    sandwich_norm=True, embedding_multiplier=D ** 0.5, num_dense_layers=1,
    intermediate_size=48, num_experts=16, num_experts_per_tok=2,
    moe_intermediate_size=24, mlp_hidden_act="silu",
    shared_expert_intermediate_size=24, shared_expert_gated=False,
    router_scores="sigmoid", use_expert_bias=True,
    routed_scaling_factor=2.826, bias_update_rate=1e-3, norm_topk_prob=True,
    experts_held=[2, 6], rms_norm_eps=1e-5, rope_parameters=ROPE)
MODEL = dict(obs_dim=T, act_dim=3, hidden=(32, 32, 32), n_atoms=11,
             v_min=0.0, v_max=10.0, torso=SMALL)
ATTENTION = {"attn_norm", "q", "k", "v", "o", "q_norm", "k_norm",
             "op_post_norm", "ff_post_norm"}
DENSE = {"mlp_norm", "w1", "w3", "w2"}
MOE = {"moe_norm", "router", "gate", "up", "down", "shared_gate",
       "shared_up", "shared_down"}
KINDS = [(0, "sliding_attention", True), (1, "sliding_attention", False),
         (2, "full_attention", False)]


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
    """``init_state`` with the gains (all four norms', the heads') and the
    routing biases moved off their initial values, so that a test sees
    them."""
    state = init_state(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 1000))

    def move(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        if names[-1] == "scale":
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
def test_a_roped_layer_type_may_name_no_rotary_embedding():
    spec = small_config().torso
    assert spec.rope_for("full_attention") is None
    assert spec.rope_for("sliding_attention") == ROPE["sliding_attention"]
    assert spec.expert_layers == (1, 2)
    assert hash(small_config()) == hash(small_config())
    assert type(small_config().build_critic().torso) \
        is torso_lib.TORSOS["trinity"] is torso_lib.TORSOS["mellum2"]
    # a missing block stays the error it was: the older files keep their check
    with pytest.raises(ValueError, match=r"rope_parameters has no block for "
                                         r"\['full_attention'\]"):
        small_config(rope_parameters={
            "sliding_attention": ROPE["sliding_attention"]})
    with pytest.raises(ValueError, match="rope_parameters has no block"):
        small_config(rope_parameters=None)
    with pytest.raises(ValueError, match="embedding_multiplier"):
        small_config(embedding_multiplier=0.0)
    with pytest.raises(ValueError, match="unknown torso keys"):
        small_config(gate_proj=True)
    # both regimes may be none, and both may be some
    assert small_config(rope_parameters={
        "sliding_attention": None, "full_attention": None}).torso.rope_for(
            "sliding_attention") is None
    both = small_config(rope_parameters={
        k: ROPE["sliding_attention"] for k in ROPE}).torso
    assert both.rope_for("full_attention") == ROPE["sliding_attention"]


def test_a_layer_has_the_leaves_its_flags_give_it():
    tree = lambda config: jax.eval_shape(  # noqa: E731
        lambda: init_state(config, jax.random.key(0))).critic_params[
            "params"]["torso"]
    layers = tree(small_config())
    assert set(layers) == {"embed", "final_norm", "layer_0", "layer_1",
                           "layer_2"}
    assert set(layers["layer_0"]) == ATTENTION | DENSE
    assert set(layers["layer_1"]) == set(layers["layer_2"]) \
        == ATTENTION | MOE
    att = layers["layer_1"]
    # a head's query, then its gate: twice as wide; the norms' gains a head
    assert att["q"]["kernel"].shape == (D, 2 * 32)
    assert att["k"]["kernel"].shape == att["v"]["kernel"].shape == (D, 16)
    assert att["q_norm"]["scale"].shape == att["k_norm"]["scale"].shape \
        == (8,)
    assert att["op_post_norm"]["scale"].shape \
        == att["ff_post_norm"]["scale"].shape == (D,)
    assert att["gate"]["kernel"].shape == (4, D, 24)
    assert att["shared_gate"]["kernel"].shape == (D, 24)
    assert "shared_expert_gate" not in att  # ungated
    assert set(att["router"]) == {"kernel", "bias"}
    assert layers["layer_0"]["w1"]["kernel"].shape == (D, 48)
    # the multiplier is on the rows read: it adds no leaf and changes none
    plain = tree(small_config(embedding_multiplier=1.0))
    assert jax.tree_util.tree_structure(plain) \
        == jax.tree_util.tree_structure(layers)
    assert plain["embed"]["kernel"].shape == (64, D)


def test_the_sixth_older_models_tree_and_program_are_the_parents():
    """``tests/test_torso_loop.py`` pins the four models before it,
    ``test_torso_nemotronh.py`` Ouro's; this is Nemotron-H's digest by the
    same recipe on the parent commit (633e3e5), with its counter in the
    differentiated sum; the program taken again at PR 50, which edits the
    expert layer every routed model shares ("1efcc88099600a6a" before)."""
    from benchmark import cellbuild

    block = cellbuild.load_config("humanoid-nemotronh-ep16", True)["model"][
        "torso"]
    torso = torso_lib.build_torso(torso_lib.TorsoSpec.from_dict(block))
    params = torso.init(jax.random.key(7))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    obs = jax.ShapeDtypeStruct((2, block["tokens"]), jnp.float32)

    def loss(p, o):
        z, aux = torso.apply(p, o, train=True)
        return jnp.sum(z) + jnp.sum(aux["ssd_kept"])

    text = jax.jit(lambda p, o: jax.value_and_grad(
        lambda p: loss(p, o))(p)).lower(params, obs).as_text()
    assert (h.hexdigest()[:16],
            hashlib.sha256(text.encode()).hexdigest()[:16]) == (
        "a84a3aaf82d303fb", "f7cfebc98593f348")


# -- each kind of layer and the whole step against the reference --------------
@pytest.mark.parametrize("index, layer_type, dense", KINDS)
def test_each_kind_of_layer_matches_the_reference(index, layer_type, dense):
    config = small_config()
    torso = config.build_critic().torso
    p = seeded_state(config, 5).critic_params["params"]["torso"][
        f"layer_{index}"]
    x = jax.random.normal(jax.random.key(index), (T, D))
    got, stats, sel = torso._sequence(p, x, layer_type, dense, True)
    want, ref_stats = rm.layer(rm.EXACT_OPS, SMALL, p, x, layer_type, dense)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    assert sel == ()
    if dense:
        assert stats == {} and ref_stats == ()
    else:
        assert set(stats) == {"route_counts", "bias_swapped"}
        np.testing.assert_array_equal(np.asarray(stats["route_counts"]),
                                      np.asarray(ref_stats[0]))
        assert int(stats["bias_swapped"]) == int(ref_stats[1]) > 0
        assert int(np.asarray(stats["route_counts"]).sum()) == 2 * T
    # every one of the four norms' gains, the heads' gains and the gate reach
    # the output: a layer with one of them put back to ones is another layer
    for name in ("attn_norm", "op_post_norm", "ff_post_norm", "q_norm",
                 "k_norm", "mlp_norm" if dense else "moe_norm"):
        off = {**p, name: {"scale": jnp.ones_like(p[name]["scale"])}}
        moved = torso._sequence(off, x, layer_type, dense, True)[0]
        assert np.abs(np.asarray(moved - got)).max() > 1e-3, name
    h = jax.random.normal(jax.random.key(9), (T, D))
    _q, _k, _v, gate = torso._qkv(p, h, layer_type)
    w_q, w_g = rm.split_gate(SMALL, p["q"]["kernel"])
    np.testing.assert_allclose(
        np.asarray(gate), np.asarray(jnp.dot(h, w_g, precision="highest")),
        rtol=1e-5, atol=1e-5)
    assert w_q.shape == w_g.shape == (D, 32)


def test_forward_pass_and_counters_match_the_reference():
    config = small_config()
    state = seeded_state(config, 2)
    batch = small_batch()
    latent, aux = config.build_critic().latent(state.critic_params,
                                               batch.obs, train=True)
    z, counts, swapped = rm.torso(
        rm.EXACT_OPS, SMALL, state.critic_params["params"]["torso"],
        batch.obs)
    np.testing.assert_allclose(np.asarray(latent), np.asarray(z), rtol=2e-4,
                               atol=2e-5)
    # a row an expert layer, nothing of the dense one
    assert set(aux) == {"route_counts", "bias_swapped"}
    assert aux["route_counts"].shape == (2, 16)
    assert aux["bias_swapped"].shape == (2,)
    np.testing.assert_array_equal(np.asarray(aux["route_counts"]),
                                  np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(aux["bias_swapped"]),
                                  np.asarray(swapped))
    assert int(np.asarray(counts).sum()) == 2 * B * T * 2
    # the multiplier reaches the latent (the first norm divides it out of the
    # branches, the residual stream carries it)
    plain = small_config(embedding_multiplier=1.0).build_critic().latent(
        state.critic_params, batch.obs)[0]
    assert np.abs(np.asarray(plain - latent)).max() > 1e-2


@pytest.mark.parametrize("control", ["all_full", "roped_full"])
def test_the_window_cuts_and_the_two_rotary_regimes_each_matter(control):
    """80 tokens under a window of 24: the reference whose window layers see
    every earlier key, and the reference that rotates on the full layer too,
    each differ from the program by far more than the 2e-4 it matches the
    sound reference to; and the program with the matching spec follows each
    control, so the difference is the mechanism's and nothing else's."""
    assert SMALL["tokens"] > SMALL["sliding_window"]
    config = small_config()
    state = seeded_state(config, 2)
    batch = small_batch()
    tree = state.critic_params["params"]["torso"]
    latent = config.build_critic().latent(state.critic_params, batch.obs)[0]
    bent = rm.torso(rm.EXACT_OPS, SMALL, tree, batch.obs, control)[0]
    gap = np.abs(np.asarray(bent - latent)).max() \
        / np.abs(np.asarray(latent)).max()
    assert gap > 50 * 2e-4, gap
    over = {"all_full": dict(sliding_window=T),
            "roped_full": dict(rope_parameters={
                k: ROPE["sliding_attention"] for k in ROPE})}[control]
    same = small_config(**over).build_critic().latent(
        state.critic_params, batch.obs)[0]
    np.testing.assert_allclose(np.asarray(same), np.asarray(bent), rtol=2e-4,
                               atol=2e-5)
    # the mask is the two inequalities: the query's own position counts
    keep = np.asarray(rm.visible(8, 0, 8, 3))
    assert keep[5].tolist() == [False, False, False, True, True, True,
                                False, False]
    assert keep.sum() == 1 + 2 + 6 * 3


def test_whole_steps_match_the_reference():
    """Two steps: losses, TD errors, counters, the gradient (Adam's first
    moment after one step is 0.1 of it; every leaf has one but the bias),
    the parameters and the biases the rule moved."""
    config = small_config()
    state = seeded_state(config, 1)
    cfg = reference.model_cfg({**MODEL, "torso": SMALL})
    st = rm.init(state.actor_params, state.critic_params)
    step = jax.jit(lambda s, b, w: update_step(config, s, b, w))

    @jax.jit
    def ref_step(st, batch, w):
        proj = rm.target(cfg, rm.EXACT_OPS, st, batch)
        grads, m = rm.critic_grads(cfg, rm.EXACT_OPS, st["critic"], batch, w,
                                   proj)
        new, m["actor_loss"] = rm.actor_update(
            cfg, rm.EXACT_OPS, rm.critic_adam(cfg, st, grads,
                                              m["route_counts"]),
            st["count"], batch)
        return new, m

    bias0 = np.asarray(state.critic_params["params"]["torso"]["layer_1"][
        "router"]["bias"])
    for t in range(2):
        batch = small_batch(10 + t)
        w = jnp.linspace(0.5, 1.0, B)
        state, m = step(state, batch, w)
        st, ref = ref_step(st, (batch.obs, batch.action, batch.reward,
                                batch.next_obs, batch.discount), w)
        assert float(m["critic_loss"]) == pytest.approx(
            float(ref["critic_loss"]), rel=1e-4)
        assert float(m["actor_loss"]) == pytest.approx(
            float(ref["actor_loss"]), rel=1e-4)
        np.testing.assert_allclose(np.asarray(m["td_error"]),
                                   np.asarray(ref["td_error"]), rtol=1e-4)
        for name in ("route_counts", "bias_swapped"):
            np.testing.assert_array_equal(np.asarray(m[name]),
                                          np.asarray(ref[name]))
        if t == 0:  # gradients leaf by leaf
            mu = state.critic_opt_state[0].mu
            assert tree_gap(mu, st["cm"]) < 5e-3
            for i in range(3):
                layer = mu["params"]["torso"][f"layer_{i}"]
                for name, leaf in layer.items():
                    for key, x in leaf.items():
                        moved = float(jnp.max(jnp.abs(x))) > 0
                        # the bias enters a top-k alone: no gradient at all
                        assert moved != (key == "bias"), (i, name, key)
    assert tree_gap(state.critic_params, st["critic"]) < 1e-3
    assert tree_gap(state.target_critic_params, st["t_critic"]) < 1e-5
    assert tree_gap(state.actor_params, st["actor"]) < 1e-3
    for i in (1, 2):  # the bias step: the program's biases are the rule's
        ours = np.asarray(state.critic_params["params"]["torso"][
            f"layer_{i}"]["router"]["bias"])
        np.testing.assert_allclose(ours, np.asarray(
            st["critic"]["params"]["torso"][f"layer_{i}"]["router"]["bias"]),
            rtol=0, atol=1e-7)
    moved = np.asarray(state.critic_params["params"]["torso"]["layer_1"][
        "router"]["bias"]) - bias0
    assert set(np.round(np.abs(moved) / 1e-3).tolist()) <= {0.0, 1.0, 2.0}
    assert np.abs(moved).max() > 1e-3  # two steps the same way somewhere


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 16 shares (one expert each), the shared expert
    (which every chip computes alike) counted once, add up to what the uncut
    reference gives for the whole layer."""
    config = small_config()
    p = seeded_state(config, 3).critic_params["params"]["torso"]["layer_1"]
    k = jax.random.split(jax.random.key(4), 4)
    full = {**p,
            "gate": {"kernel": jax.random.normal(k[0], (16, D, 24))
                     / D ** 0.5},
            "up": {"kernel": jax.random.normal(k[1], (16, D, 24)) / D ** 0.5},
            "down": {"kernel": jax.random.normal(k[2], (16, 24, D))
                     / 24 ** 0.5}}
    h = jax.random.normal(k[3], (T, D))
    whole, counts, _sw = rm.moe_ff(rm.EXACT_OPS, SMALL, full, h, held=(0, 16))
    shared = rm.swiglu(rm.EXACT_OPS, h, p["shared_gate"]["kernel"],
                       p["shared_up"]["kernel"], p["shared_down"]["kernel"])
    total = jnp.zeros_like(h)
    for lo in range(16):
        spec = torso_lib.TorsoSpec.from_dict({**SMALL,
                                              "experts_held": [lo, lo + 1]})
        part = {**p, **{name: {"kernel": full[name]["kernel"][lo:lo + 1]}
                        for name in ("gate", "up", "down")}}
        out, stats = torso_lib.expert_share(spec, part, h, jnp.float32)
        np.testing.assert_array_equal(np.asarray(stats["route_counts"]),
                                      np.asarray(counts))
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole),
                               rtol=2e-4, atol=2e-5)
    # the routed weights carry route_scale: a token's sum to 2.826; the
    # program's 1e-6 in that sum against afmoe's 1e-20 moves a weight by
    # under 1e-6 of itself (two sigmoid scores sum to order 1)
    w, _e, _c, _s = rm.route(SMALL, h, p["router"])
    np.testing.assert_allclose(np.asarray(jnp.sum(w, -1)), 2.826, rtol=1e-6)
    ours = torso_lib.route(config.torso, h, p["router"])[0]
    assert 0 < np.abs(np.asarray(ours / w) - 1).max() < 3e-6
    assert np.abs(np.asarray(whole - shared)).max() > 1e-2


def test_train_main_runs_the_benchmark_files_rehearsal_torso(tmp_path):
    """``--torso`` with the explicit ``null`` rope block and the multiplier,
    at the configuration file's rehearsal sizes, through ``train.main``:
    init_state -> FusedDeviceReplay -> FusedLoop, finite losses, the chunk
    still ``jit_fn`` with every scope the cell's readers read in it."""
    import json

    from benchmark import cellbuild
    from d4pg_tpu import train
    from d4pg_tpu.obs import trace as program

    cfg = cellbuild.load_config("humanoid-trinity-ep16", True)
    block = cfg["model"]["torso"]
    assert block["layer_types"] == ["sliding_attention", "sliding_attention",
                                    "full_attention"]
    assert block["rope_parameters"]["full_attention"] is None
    assert block["tokens"] > block["sliding_window"]
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
    for scope in ("torso.embed", "torso.attn_window", "torso.attn_full",
                  "torso.mlp", "torso.route", "torso.experts",
                  "torso.shared_expert", "torso.pool"):
        assert scope in text, scope
    assert "torso.conv" not in text and "torso.mamba" not in text
