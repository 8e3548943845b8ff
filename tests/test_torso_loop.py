"""The looped torso (``models/torso.py`` with ``total_ut_steps`` > 1, Ouro's):
the keys that tell it apart as data, the seam with the four older models
(trees and traced programs bit for bit the parent's), the loop against tied
copies of the stack applied in turn, the exit distribution and the
expected-exit loss, the dense-only torso, and the program against
``benchmark/reference_loop.py`` layer by layer, pass by pass and over whole
steps, through ``update_step``, the fused chunk and ``train.main``."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_loop as rl
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.update import (
    act_deterministic,
    policy_params,
    update_step,
)
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch

ROPE = {"full_attention": {"rope_type": "default", "rope_theta": 1000000}}
T, R, LAYERS = 48, 3, 2
SMALL = dict(
    name="ouro", tokens=T, vocab_rows=64, bins=16, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    layer_types=["full_attention"] * LAYERS, num_dense_layers=LAYERS,
    intermediate_size=96, num_experts=0, num_experts_per_tok=0,
    moe_intermediate_size=0, experts_held=[0, 0], rms_norm_eps=1e-6,
    sandwich_norm=True, total_ut_steps=R, early_exit_threshold=1,
    exit_entropy_beta=0.05, rope_parameters=ROPE)
MODEL = dict(obs_dim=T, act_dim=3, hidden=(32, 32, 32), n_atoms=11,
             v_min=0.0, v_max=10.0, torso=SMALL)
B = 3


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
    """``init_state`` with the norms' gains moved off 1 and the gate's kernel
    and bias made large enough to tell the passes apart, so that a test sees
    each of them."""
    state = init_state(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 1000))

    def move(path, x):
        names = [str(getattr(k, "key", k)) for k in path]
        if names[-1] == "scale":
            return x + 0.3 * jax.random.normal(next(keys), x.shape)
        if "exit_gate" in names:
            return 8.0 * x if names[-1] == "kernel" else x + 0.2
        return x

    critic = jax.tree_util.tree_map_with_path(move, state.critic_params)
    return state._replace(
        critic_params=critic,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic))


def tree_gap(a, b):
    diff = jax.tree_util.tree_map(lambda x, y: x - y, a, b)
    return float(np.max(reference.leaf_norms(diff)
                        / np.maximum(reference.leaf_norms(b), 1e-12)))


def torso_of(config):
    return config.build_critic().torso


def torso_params(state):
    return state.critic_params["params"]["torso"]


# -- the keys -----------------------------------------------------------------
def test_spec_takes_the_loop_the_second_norms_and_no_experts_as_data():
    spec = small_config().torso
    assert spec.total_ut_steps == R and spec.sandwich_norm
    assert spec.num_experts == 0 and spec.n_held == 0
    assert spec.expert_layers == ()
    hash(spec)  # part of the jit-static config
    assert "ouro" in torso_lib.TORSOS
    # the defaults are the four older models'
    fields = {f.name: f.default
              for f in dataclasses.fields(torso_lib.TorsoSpec)}
    assert fields["total_ut_steps"] == 1 and fields["sandwich_norm"] is False
    assert fields["exit_entropy_beta"] == 0.0
    assert fields["early_exit_threshold"] == 1.0
    for bad, why in (
            (dict(total_ut_steps=0), "at least 1"),
            (dict(early_exit_threshold=0.5), "not implemented"),
            (dict(exit_entropy_beta=-0.1), "entropy bonus"),
            (dict(experts_held=[0, 1]), "holds none"),
            (dict(num_dense_layers=1), "every layer of it is dense"),
            (dict(num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
                  moe_intermediate_size=32, experts_held=[0, 4]),
             "no counters"),
            (dict(no_such_key=1), "unknown torso keys")):
        with pytest.raises(ValueError, match=why):
            small_config(**bad)
    # an expert model still needs an expert layer
    with pytest.raises(ValueError, match="leaves no expert layer"):
        small_config(total_ut_steps=1, num_experts=8, num_experts_per_tok=2,
                     moe_intermediate_size=32, experts_held=[0, 4])


def test_a_looped_layer_and_torso_have_the_leaves_their_kind_has():
    tree = torso_params(init_state(small_config(), jax.random.key(0)))
    assert set(tree) == {"embed", "final_norm", "exit_gate", "layer_0",
                         "layer_1"}
    assert set(tree["layer_0"]) == {
        "attn_norm", "q", "k", "v", "o", "op_post_norm", "mlp_norm", "w1",
        "w3", "w2", "ff_post_norm"}
    assert tree["exit_gate"]["kernel"].shape == (64, 1)
    assert tree["exit_gate"]["bias"].shape == (1,)
    assert float(tree["exit_gate"]["bias"][0]) == 0.0
    assert float(jnp.std(tree["exit_gate"]["kernel"])) == pytest.approx(
        1 / 8, rel=0.3)
    # one pass and no second norms: neither the gate nor the gains, and the
    # other leaves draw what they draw in the looped tree
    plain = torso_params(init_state(
        small_config(total_ut_steps=1, sandwich_norm=False),
        jax.random.key(0)))
    assert "exit_gate" not in plain
    assert set(tree["layer_0"]) - set(plain["layer_0"]) == {
        "op_post_norm", "ff_post_norm"}
    for name in ("q", "o", "w2"):
        np.testing.assert_array_equal(
            np.asarray(plain["layer_1"][name]["kernel"]),
            np.asarray(tree["layer_1"][name]["kernel"]))


# digests taken on the parent commit (503cdd4): ``init`` of the four older
# models at their configuration files' rehearsal sizes, and the StableHLO text
# of ``apply`` differentiated (the program as traced: no device, no compiler).
# ``humanoid-keye2-ep8``'s program was taken again at PR 42, which edits that
# layer alone (the alignment loss is traced after the attention whose
# log-sum-exp it can now be handed; "7588efd0692b8543" before), and
# ``humanoid-qwen3next-ep32``'s at PR 47, which edits its scan alone
# (``ops/delta_rule.py``: the solve's blocks with the batch last, the
# products of keys a key head; "ad071692d07dcb4e" before); all four programs
# were taken again at PR 50, which edits the expert layer they share (``route``
# selects by reductions, ``expert_share`` places without a scatter;
# "2d2d8eaea6f42e6a", "70ec5a3705b72e2d", "a3d52dcb90b967fb",
# "3993bba7799b77dd" before, in this order); the trees are as they were
PARENT = {
    "humanoid-mellum2-ep4": ("5f8baada6f98f565", "dd637b4a6bc6ad6f"),
    "humanoid-keye2-ep8": ("85b75a256c67cb06", "229928489756fbec"),
    "humanoid-lfm2-ep4": ("8b83ae2d356957ee", "d933c3f4c29d22f1"),
    "humanoid-qwen3next-ep32": ("5f8f51228059fb1e", "1d2d3c5742a08d09")}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_four_older_models_trees_and_programs_are_the_parents(name):
    from benchmark import cellbuild

    block = cellbuild.load_config(name, True)["model"]["torso"]
    torso = torso_lib.build_torso(torso_lib.TorsoSpec.from_dict(block))
    params = torso.init(jax.random.key(7))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    obs = jax.ShapeDtypeStruct((2, block["tokens"]), jnp.float32)

    def loss(p, o):
        z, aux = torso.apply(p, o, train=True)
        return jnp.sum(z) + aux.get("index_loss", 0.0)

    text = jax.jit(lambda p, o: jax.value_and_grad(
        lambda p: loss(p, o))(p)).lower(params, obs).as_text()
    assert (h.hexdigest()[:16],
            hashlib.sha256(text.encode()).hexdigest()[:16]) == PARENT[name]


def test_one_pass_without_the_second_norms_is_the_plain_path():
    """``total_ut_steps`` 1 and ``sandwich_norm`` off: every layer once as
    ``x + Op(Norm(x))``, ``x + FF(Norm(x))``, the final norm, the mean; no
    gate, nothing handed up."""
    config = small_config(total_ut_steps=1, sandwich_norm=False)
    torso = torso_of(config)
    p = torso_params(seeded_state(config, 3))
    obs = small_batch().obs
    latent, aux = torso.apply(p, obs, train=True)
    assert aux == {}
    x = p["embed"]["kernel"][torso_lib.tokenise(config.torso, obs)]
    for i in range(LAYERS):
        lay = p[f"layer_{i}"]
        h = torso_lib.rms_norm(x, lay["attn_norm"]["scale"], 1e-6)
        x = x + jnp.stack([rl.attention_op(rl.EXACT_OPS, SMALL, lay, hs)
                           for hs in h])
        h = torso_lib.rms_norm(x, lay["mlp_norm"]["scale"], 1e-6)
        x = x + jnp.stack([rl.dense_ff(rl.EXACT_OPS, lay, hs) for hs in h])
    x = torso_lib.rms_norm(x, p["final_norm"]["scale"], 1e-6)
    np.testing.assert_allclose(np.asarray(latent),
                               np.asarray(jnp.mean(x, axis=1)), rtol=2e-4,
                               atol=2e-5)


# -- the loop -----------------------------------------------------------------
def in_turn(torso, copies, final_norm, x):
    """``len(copies)`` copies of the stack applied in turn, the final norm
    behind each: ``[u_1, ..., u_R]``."""
    latents = []
    for p in copies:
        x = torso._stack(p, x, False)[0]
        x = torso_lib.rms_norm(x, final_norm, 1e-6)
        latents.append(jnp.mean(x, axis=1))
    return jnp.stack(latents)


def test_r_passes_are_r_tied_copies_of_the_stack_applied_in_turn():
    """The output of the loop equals R copies of the stack with tied weights
    applied one after another, ``final_norm`` behind every one of them (the
    normed state feeds the next pass: leaving it out, or norming only at the
    end, is another function), and every leaf's gradient is the sum of the R
    untied copies' gradients."""
    config = small_config()
    torso = torso_of(config)
    p = torso_params(seeded_state(config, 4))
    obs = small_batch().obs
    x0 = p["embed"]["kernel"][torso_lib.tokenise(config.torso, obs)]
    layers = {k: v for k, v in p.items() if k.startswith("layer_")}
    weigh = jax.random.normal(jax.random.key(8), (R, B, 64))

    def looped(layers, final_norm):
        latents, _logits = torso._passes(
            {**p, **layers, "final_norm": {"scale": final_norm}}, x0)
        return latents

    def untied(copies, final_norm):
        return in_turn(torso, copies, final_norm, x0)

    scale = p["final_norm"]["scale"]
    got = looped(layers, scale)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(untied([layers] * R, scale)),
                               rtol=1e-5, atol=1e-6)
    # the first pass alone is the one-pass torso's latent
    one = torso_of(small_config(total_ut_steps=1))
    first, _aux = one.apply({k: v for k, v in p.items() if k != "exit_gate"},
                            obs)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(first),
                               rtol=1e-5, atol=1e-6)
    # Norm_f between the passes matters: norming at the end only differs
    x = x0
    for _ in range(R):
        x = torso._stack(layers, x, False)[0]
    late = jnp.mean(torso_lib.rms_norm(x, scale, 1e-6), axis=1)
    assert float(jnp.max(jnp.abs(late - got[-1]))) > 1e-3
    # gradients: tied = the sum over the untied copies
    g_tied, g_norm = jax.grad(
        lambda l, s: jnp.sum(weigh * looped(l, s)), argnums=(0, 1))(
            layers, scale)
    g_copies, g_norm_u = jax.grad(
        lambda c, s: jnp.sum(weigh * untied(c, s)), argnums=(0, 1))(
            [layers] * R, scale)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *g_copies)
    assert tree_gap(g_tied, summed) < 1e-4
    np.testing.assert_allclose(np.asarray(g_norm), np.asarray(g_norm_u),
                               rtol=1e-4, atol=1e-6)
    # every copy's share is there: no pass's gradient is zero or the whole
    for g in g_copies:
        assert 0.02 < float(reference.leaf_norms(g).sum()
                            / reference.leaf_norms(summed).sum()) < 2.0


def test_apply_hands_up_every_pass_under_train_and_the_last_one_always():
    config = small_config()
    critic = config.build_critic()
    state = seeded_state(config, 2)
    obs = small_batch().obs
    latent, aux = critic.latent(state.critic_params, obs, train=True)
    assert set(aux) == {"pass_latents", "exit_logits"}
    assert aux["pass_latents"].shape == (R, B, 64)
    assert aux["exit_logits"].shape == (R, B)
    np.testing.assert_array_equal(np.asarray(latent),
                                  np.asarray(aux["pass_latents"][-1]))
    plain, none = critic.latent(state.critic_params, obs)
    assert none == {}
    np.testing.assert_allclose(np.asarray(plain), np.asarray(latent),
                               rtol=1e-6, atol=1e-6)
    # the passes differ, and so do their gate logits
    u = np.asarray(aux["pass_latents"])
    assert np.abs(u[0] - u[1]).max() > 1e-2 < np.abs(u[1] - u[2]).max()
    # acting reads the last pass
    action = act_deterministic(config, policy_params(config, state), obs)
    want = config.build_actor().apply(state.actor_params, latent)
    np.testing.assert_allclose(np.asarray(action), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# -- the exit distribution and the loss ---------------------------------------
def test_the_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    logits = jnp.asarray([[0.3, -2.0, 4.0, 0.0], [1.0, 0.5, -3.0, 0.0],
                          [-0.7, 2.0, 0.1, 3.0], [5.0, -5.0, 9.0, -40.0]])
    p, entropy = torso_lib.exit_distribution(logits)
    assert p.shape == (4, 4) and entropy.shape == (4,)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0,
                               atol=1e-6)
    lam = np.asarray(jax.nn.sigmoid(logits), np.float64)
    want = np.stack([lam[0], lam[1] * (1 - lam[0]),
                     lam[2] * (1 - lam[0]) * (1 - lam[1]),
                     (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    np.testing.assert_allclose(np.asarray(p), want, rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(np.asarray(p),
                               np.asarray(rl.exit_distribution(logits)),
                               rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(
        np.asarray(entropy), -np.sum(want * np.log(want), axis=0), rtol=1e-5)
    # lambda_R is not read: the last row's logits move nothing
    g = jax.grad(lambda l: jnp.sum(
        jnp.arange(1.0, 5.0)[:, None] * torso_lib.exit_distribution(l)[0])
        + jnp.sum(torso_lib.exit_distribution(l)[1]))(logits)
    assert np.all(np.asarray(g[-1]) == 0.0)
    assert np.all(np.abs(np.asarray(g[:-1])) > 0.0)
    # a gate at zero: 0.5, 0.25, 0.125 and what is left, 0.125
    even, _h = torso_lib.exit_distribution(jnp.zeros((4, 1)))
    np.testing.assert_allclose(np.asarray(even[:, 0]),
                               [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
    # saturated gates stay finite, in value and in gradient
    hard = jnp.asarray([[80.0], [-80.0], [0.0]])
    p, h = torso_lib.exit_distribution(hard)
    grads = jax.grad(
        lambda l: jnp.sum(torso_lib.exit_distribution(l)[1]))(hard)
    assert np.all(np.isfinite(np.asarray(p))) and np.isfinite(float(h[0]))
    assert np.all(np.isfinite(np.asarray(grads)))


def test_the_critic_loss_is_the_expected_td_loss_less_the_entropy_bonus():
    """``critic_loss`` is the first term alone, ``td_error`` the last
    pass's, the counters the mean exit distribution and the weighted loss a
    pass; the gate and every shared leaf get a gradient, the gate's last
    logit none."""
    from d4pg_tpu.learner.update import _expected_exit_loss

    config = small_config(exit_entropy_beta=0.7)
    critic = config.build_critic()
    state = seeded_state(config, 6)
    batch = small_batch(3)
    w = jnp.linspace(0.4, 1.0, B)
    proj = jax.nn.softmax(jax.random.normal(jax.random.key(2), (B, 11)))

    def parts(params):
        _z, aux = critic.latent(params, batch.obs, train=True)
        return _expected_exit_loss(critic, params, proj, batch.action, w,
                                   0.7, aux["pass_latents"],
                                   aux["exit_logits"]), aux

    (total, (first, td, counters)), aux = parts(state.critic_params)
    p, entropy = torso_lib.exit_distribution(aux["exit_logits"])
    per_pass = np.stack([np.asarray(-jnp.sum(proj * jnp.log(
        critic.of_latent(state.critic_params, z, batch.action) + 1e-10),
        axis=-1)) for z in aux["pass_latents"]])
    want_first = float(np.mean(np.asarray(w) * np.sum(
        np.asarray(p) * per_pass, axis=0)))
    assert float(first) == pytest.approx(want_first, rel=1e-5)
    assert float(total) == pytest.approx(
        want_first - 0.7 * float(jnp.mean(entropy)), rel=1e-5)
    assert float(total) < float(first)  # the bonus is subtracted
    np.testing.assert_allclose(np.asarray(td), per_pass[-1], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(counters["exit_dist"]),
                               np.mean(np.asarray(p), axis=1), rtol=1e-6)
    assert float(jnp.sum(counters["exit_dist"])) == pytest.approx(1.0,
                                                                   abs=1e-6)
    np.testing.assert_allclose(
        np.asarray(counters["loss_by_pass"]),
        np.mean(np.asarray(w) * per_pass, axis=1), rtol=1e-5)
    grads = jax.grad(lambda q: parts(q)[0][0])(state.critic_params)
    torso = grads["params"]["torso"]
    for path, g in jax.tree_util.tree_flatten_with_path(torso)[0]:
        assert float(jnp.max(jnp.abs(g))) > 0, jax.tree_util.keystr(path)
    assert float(jnp.max(jnp.abs(jax.tree_util.tree_leaves(
        grads["params"]["critic"])[0]))) > 0


def test_raising_beta_flattens_the_exit_distribution():
    """The entropy term's sign: the same steps from the same state, with a
    large ``exit_entropy_beta`` and with none, from a gate that leaves
    early (bias 2: about 0.88 / 0.10 / 0.01). The bonus pushes the exit
    distribution of every row towards uniform."""
    def entropy_after(beta, steps=8):
        config = D4PGConfig(**{**MODEL, "lr_critic": 0.03, "torso": {
            **SMALL, "exit_entropy_beta": beta}})
        state = init_state(config, jax.random.key(7))
        critic = state.critic_params
        gate = critic["params"]["torso"]["exit_gate"]
        critic = {"params": {**critic["params"], "torso": {
            **critic["params"]["torso"],
            "exit_gate": {**gate, "bias": gate["bias"] + 2.0}}}}
        state = state._replace(critic_params=critic)
        step = jax.jit(lambda s, b, w: update_step(config, s, b, w))
        for t in range(steps):
            state, m = step(state, small_batch(20 + t), jnp.ones((B,)))
        _z, aux = config.build_critic().latent(
            state.critic_params, small_batch(99).obs, train=True)
        return float(jnp.mean(torso_lib.exit_distribution(
            aux["exit_logits"])[1])), np.asarray(m["exit_dist"])

    flat, dist = entropy_after(20.0)
    plain, _dist = entropy_after(0.0)
    assert flat > plain + 0.02, (flat, plain)
    assert flat <= np.log(R) + 1e-6
    assert dist.sum() == pytest.approx(1.0, abs=1e-5)


# -- the dense-only torso -----------------------------------------------------
def test_a_torso_without_experts_has_no_router_and_balance_leaves_it():
    config = small_config()
    critic = config.build_critic()
    state = seeded_state(config, 1)
    for layer in torso_params(state).values():
        assert not {"router", "gate", "up", "down", "moe_norm"} & set(layer)
    same = critic.balance(state.critic_params, None)
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(same),
        jax.tree_util.tree_leaves(state.critic_params), strict=True))
    assert critic.torso.balance(torso_params(state), None) \
        is torso_params(state)
    _state, m = jax.jit(lambda s, b, w: update_step(config, s, b, w))(
        state, small_batch(), jnp.ones((B,)))
    assert set(m) == {"critic_loss", "actor_loss", "q_mean", "td_error",
                      "exit_dist", "loss_by_pass"}
    # one pass and no experts: the plain metrics, no counter at all
    plain = small_config(total_ut_steps=1)
    _state, m = jax.jit(lambda s, b, w: update_step(plain, s, b, w))(
        init_state(plain, jax.random.key(0)), small_batch(), jnp.ones((B,)))
    assert set(m) == {"critic_loss", "actor_loss", "q_mean", "td_error"}


# -- against the reference ----------------------------------------------------
def test_a_layer_matches_the_reference_with_all_four_norms():
    config = small_config()
    torso = torso_of(config)
    p = torso_params(seeded_state(config, 5))["layer_1"]
    x = jax.random.normal(jax.random.key(1), (T, 64))
    got, stats, selected = torso._sequence(p, x, "full_attention", True,
                                           True)
    assert stats == {} and selected == ()
    want = rl.layer(rl.EXACT_OPS, SMALL, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    # each of the four gains reaches the output
    for name in ("attn_norm", "op_post_norm", "mlp_norm", "ff_post_norm"):
        moved = {**p, name: {"scale": p[name]["scale"] * 1.5}}
        other = torso._sequence(moved, x, "full_attention", True, True)[0]
        assert float(jnp.max(jnp.abs(other - got))) > 1e-2, name
        np.testing.assert_allclose(
            np.asarray(other), np.asarray(rl.layer(rl.EXACT_OPS, SMALL,
                                                   moved, x)),
            rtol=2e-4, atol=2e-5)


def test_every_pass_matches_the_reference():
    config = small_config()
    state = seeded_state(config, 2)
    obs = small_batch().obs
    p = torso_params(state)
    _latent, aux = config.build_critic().latent(state.critic_params, obs,
                                                train=True)
    latents, logits = rl.torso(rl.EXACT_OPS, SMALL, p, obs)
    for r in range(R):  # pass by pass
        np.testing.assert_allclose(np.asarray(aux["pass_latents"][r]),
                                   np.asarray(latents[r]), rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(aux["exit_logits"]),
                               np.asarray(logits), rtol=2e-4, atol=2e-5)
    # the reference's passes are its layers applied in a Python loop, the
    # normed state fed on
    xs = rl.passes(rl.EXACT_OPS, SMALL, p, obs)
    assert len(xs) == R and xs[0].shape == (B, T, 64)
    x = xs[0]
    for i in range(LAYERS):
        x = jnp.stack([rl.layer(rl.EXACT_OPS, SMALL, p[f"layer_{i}"], s)
                       for s in x])
    np.testing.assert_allclose(
        np.asarray(rl.rt.rms(x, p["final_norm"]["scale"], 1e-6)),
        np.asarray(xs[1]), rtol=1e-4, atol=1e-5)


def ref_steps(config, state, detach, steps=2):
    cfg = reference.model_cfg({**MODEL, "torso": SMALL})
    st = rl.init(state.actor_params, state.critic_params)
    key = jax.random.key(9)
    ref_step = jax.jit(lambda st, b, w, key: rl.step(
        cfg, rl.EXACT_OPS, st, b, w, key, detach))
    out = []
    for t in range(steps):
        batch = small_batch(10 + t)
        st, rm, key = ref_step(st, (
            batch.obs, batch.action, batch.reward, batch.next_obs,
            batch.discount), jnp.linspace(0.5, 1.0, B), key)
        out.append((jax.tree_util.tree_map(np.asarray, rm),
                    jax.device_get(st["cm"])))
    return out, st


def test_whole_steps_match_the_reference():
    """Two steps: losses, TD errors, both counters, the gradient (Adam's
    first moment after one step is 0.1 of it), the parameters."""
    config = small_config()
    state = seeded_state(config, 1)
    refs, st = ref_steps(config, state, False)
    step = jax.jit(lambda s, b, w: update_step(config, s, b, w))
    for t, (rm, cm) in enumerate(refs):
        state, m = step(state, small_batch(10 + t), jnp.linspace(0.5, 1.0, B))
        assert float(m["critic_loss"]) == pytest.approx(
            float(rm["critic_loss"]), rel=1e-4)
        assert float(m["actor_loss"]) == pytest.approx(
            float(rm["actor_loss"]), rel=1e-4)
        np.testing.assert_allclose(np.asarray(m["td_error"]),
                                   rm["td_error"], rtol=1e-4)
        np.testing.assert_allclose(np.asarray(m["exit_dist"]),
                                   rm["exit_dist"], rtol=1e-4)
        np.testing.assert_allclose(np.asarray(m["loss_by_pass"]),
                                   rm["loss_by_pass"], rtol=1e-4)
        if t == 0:
            mu = state.critic_opt_state[0].mu
            assert tree_gap(mu, cm) < 5e-3
            gate = mu["params"]["torso"]["exit_gate"]
            assert float(jnp.max(jnp.abs(gate["kernel"]))) > 0
            assert float(jnp.abs(gate["bias"][0])) > 0
    assert tree_gap(state.critic_params, st["critic"]) < 1e-3
    assert tree_gap(state.target_critic_params, st["t_critic"]) < 1e-5
    assert tree_gap(state.actor_params, st["actor"]) < 1e-3


def test_the_detach_control_differs_in_gradients_and_not_forward():
    """A stop-gradient between passes leaves the first step's every forward
    number alone and changes what the backward across passes feeds: the
    embedding's and the layers' first moments."""
    config = small_config()
    state = init_state(config, jax.random.key(1))
    (sound, cm), = ref_steps(config, state, False, steps=1)[0]
    (cut, cm_cut), = ref_steps(config, state, True, steps=1)[0]
    for name in ("critic_loss", "td_error", "exit_dist", "loss_by_pass",
                 "actor_loss"):
        np.testing.assert_allclose(cut[name], sound[name], rtol=2e-3
                                   if name == "actor_loss" else 1e-6)
    embed = lambda t: np.asarray(  # noqa: E731
        t["params"]["torso"]["embed"]["kernel"])
    gap = np.linalg.norm(embed(cm_cut) - embed(cm)) / np.linalg.norm(
        embed(cm))
    assert gap > 0.2, gap
    layer = lambda t: t["params"]["torso"]["layer_0"]  # noqa: E731
    assert tree_gap(layer(cm_cut), layer(cm)) > 0.05
    # the heads and the gate see every pass's latent directly: unchanged
    assert tree_gap(cm_cut["params"]["critic"], cm["params"]["critic"]) < 1e-5
    gate = lambda t: t["params"]["torso"]["exit_gate"]  # noqa: E731
    assert tree_gap(gate(cm_cut), gate(cm)) < 1e-5


# -- the chunk and the entry point --------------------------------------------
def test_fused_chunk_reports_the_exit_counters_per_step_and_no_routing():
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
    assert "route_counts" not in m and "bias_swapped" not in m
    assert m["exit_dist"].shape == m["loss_by_pass"].shape == (k, R)
    assert m["exit_dist"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(m["exit_dist"]).sum(-1), 1.0,
                               atol=1e-5)
    assert np.all(np.isfinite(np.asarray(m["critic_loss"])))
    assert np.all(np.asarray(m["loss_by_pass"]) > 0)


def test_train_main_runs_the_benchmark_files_rehearsal_torso(tmp_path):
    """``--torso`` with the looped torso's keys, at the configuration file's
    rehearsal sizes, through ``train.main``: init_state -> FusedDeviceReplay
    -> FusedLoop, finite losses, the chunk still ``jit_fn``."""
    from benchmark import cellbuild
    from d4pg_tpu import train
    from d4pg_tpu.obs import trace as program

    cfg = cellbuild.load_config("humanoid-ouro-ut4", True)
    block = cfg["model"]["torso"]
    assert len(block["layer_types"]) >= 2 and block["total_ut_steps"] == 3
    assert block["sandwich_norm"] is True and block["num_experts"] == 0
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
    for scope in ("torso.attn_full", "torso.mlp", "torso.exit"):
        assert scope in text, scope
    assert "torso.route" not in text and "torso.experts" not in text
