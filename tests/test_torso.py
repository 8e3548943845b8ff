"""The sequence torso (``d4pg_tpu/models/torso.py``) at a small size on the
CPU against the plain reference (``benchmark/reference_torso.py``): forward
pass, the whole gradient step, the blockwise attention and its gradient, the
rotary tables, the tokeniser, and the expert layer's share (the parts the
four shares give add up to the uncut layer; no assignment is lost however
the router leans). Sizes: hidden 64, 4 query heads on 2 key/value heads of
16, 8 experts top-2 of width 32, window 8, 32 tokens, one period, 64
embedding rows."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_torso as rt
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.update import update_step
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.ops import attention as attn_ops
from d4pg_tpu.parallel import partition
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
}
SMALL = dict(
    name="mellum2", tokens=32, vocab_rows=64, bins=16, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_types=["sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"],
    sliding_window=8, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, experts_held=[2, 6],
    rope_parameters=ROPE)
MODEL = dict(obs_dim=32, act_dim=3, hidden=(32, 32, 32), n_atoms=11,
             v_min=0.0, v_max=10.0, torso=SMALL)
B = 4


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


def tree_gap(a, b):
    """Largest leaf-wise ``|a - b| / |b|`` (norms)."""
    diff = jax.tree_util.tree_map(lambda x, y: x - y, a, b)
    return float(np.max(reference.leaf_norms(diff)
                        / np.maximum(reference.leaf_norms(b), 1e-12)))


# -- the seam -----------------------------------------------------------------
def test_config_freezes_the_torso_block_and_names_it():
    config = small_config()
    assert isinstance(config.torso, torso_lib.TorsoSpec)
    assert hash(config) == hash(small_config())
    assert config.torso.rope_for("full_attention")["factor"] == 16
    assert isinstance(config.build_critic(), torso_lib.TorsoCritic)
    with pytest.raises(ValueError, match="unknown torso"):
        small_config(name="no-such-torso")
    with pytest.raises(ValueError, match="tokens"):
        D4PGConfig(**{**MODEL, "obs_dim": 31})
    with pytest.raises(ValueError, match="experts_held"):
        small_config(experts_held=[6, 9])


def test_torso_lives_in_the_critic_tree_only():
    state = init_state(small_config(), jax.random.key(0))
    assert set(state.critic_params["params"]) == {"torso", "critic"}
    assert "torso" not in state.actor_params["params"]
    # the actor's head reads the latent
    assert state.actor_params["params"]["fc1"]["kernel"].shape == (64, 32)


def test_state_of_the_benchmark_configuration_is_20_bytes_a_parameter():
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-mellum2-ep4.json")) as f:
        model = json.load(f)["model"]
    config = D4PGConfig(**model)
    state = jax.eval_shape(lambda: init_state(config, jax.random.key(0)))
    leaves = jax.tree_util.tree_leaves
    n = sum(x.size for x in leaves(state.critic_params)
            + leaves(state.actor_params))
    held = sum(x.size * x.dtype.itemsize for x in leaves(
        (state.actor_params, state.critic_params, state.target_actor_params,
         state.target_critic_params, state.actor_opt_state,
         state.critic_opt_state)))
    with_gradient = held + 4 * n
    assert abs(with_gradient / (20 * n) - 1) < 0.01
    assert 530e6 < n < 550e6


# -- tokens and rotary tables -------------------------------------------------
def test_tokeniser_is_gatos_and_matches_the_reference():
    spec = small_config(bins=1024, vocab_rows=1024).torso
    v = jnp.asarray([-1e9, -3.0, -1.0, -0.01, 0.0, 0.01, 1.0, 3.0, 1e9])
    got = np.asarray(torso_lib.tokenise(spec, v))
    assert got[0] == 0 and got[-1] == 1023 and got[4] == 512
    assert np.all(np.diff(got) >= 0) and len(set(got.tolist())) == 9
    # mu-law by hand: sign(x) ln(100 |x| + 1) / ln(25601)
    by_hand = math.floor((math.log(101.0) / math.log(25601.0) + 1) * 512)
    assert got[6] == by_hand
    x = jax.random.normal(jax.random.key(0), (4096,))
    np.testing.assert_array_equal(
        np.asarray(torso_lib.tokenise(spec, x)),
        np.asarray(rt.tokenise({"bins": 1024}, x)))


def test_yarn_inv_freq_against_numbers_worked_by_hand():
    # d(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000): d(32) = 18.08,
    # d(1) = 34.98, so low 18, high 35, ramp_i = clip((i - 18) / 17, 0, 1)
    by_hand = {0: 1.0, 18: 0.024955408670558694, 26: 0.0027043825167258223,
               35: 4.7781061769823416e-05, 63: 1.5344629944572555e-07}
    inv, factor = torso_lib.rope_inv_freq(ROPE["full_attention"], 128)
    ref_inv, ref_factor = rt.inv_freq(ROPE["full_attention"], 128)
    for i, want in by_hand.items():
        assert inv[i] == pytest.approx(want, rel=1e-12)
        assert ref_inv[i] == pytest.approx(want, rel=1e-12)
    assert factor == ref_factor == 1.2772588722239782
    plain, one = torso_lib.rope_inv_freq(ROPE["sliding_attention"], 128)
    assert one == 1.0 and plain[26] == pytest.approx(0.004839421345719893)


# -- attention ----------------------------------------------------------------
def naive_attention(q, k, v, window):
    """``q [B, Hkv, G, T, D]``, ``k, v [B, Hkv, T, D]`` with a dense mask."""
    t_len = q.shape[-2]
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k, precision=reference.HI)
    t = jnp.arange(t_len)[:, None]
    pos = jnp.arange(t_len)[None, :]
    keep = pos <= t
    if window is not None:
        keep &= pos > t - window
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhgqk,bhkd->bhgqd", p, v, precision=reference.HI)


@pytest.mark.parametrize("t_len", [4, 8, 24], ids=["below", "at", "above"])
@pytest.mark.parametrize("window", [8, None], ids=["window", "full"])
def test_blockwise_attention_and_its_gradient_match_a_naive_mask(t_len,
                                                                 window):
    k = jax.random.split(jax.random.key(t_len), 4)
    q = jax.random.normal(k[0], (2, 2, 2, t_len, 16))
    kk = jax.random.normal(k[1], (2, 2, t_len, 16))
    v = jax.random.normal(k[2], (2, 2, t_len, 16))
    ct = jax.random.normal(k[3], q.shape)

    def run(fn):
        out, back = jax.vjp(fn, q, kk, v)
        return (out,) + back(ct)

    got = run(lambda q, k, v: attn_ops.causal_attention(
        q, k, v, window=window, impl="blockwise", block=4))
    want = run(lambda q, k, v: naive_attention(q, k, v, window))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5)


# -- forward pass and the whole step ------------------------------------------
def test_forward_pass_matches_the_reference():
    config = small_config()
    params = init_state(config, jax.random.key(3)).critic_params
    obs = small_batch().obs
    latent, aux = config.build_critic().latent(params, obs)
    counts = aux["route_counts"]
    assert set(aux) == {"route_counts"}  # no sparse layer: no other counter
    want, want_counts = rt.torso(rt.EXACT_OPS, SMALL,
                                 params["params"]["torso"], obs)
    np.testing.assert_allclose(np.asarray(latent), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))
    assert counts.shape == (4, 8)
    assert np.all(np.asarray(counts).sum(axis=1) == B * 32 * 2)


def test_whole_step_matches_the_reference():
    """Losses, TD errors, routing, gradients as Adam's first moments took
    them, second moments, parameters and targets after one step."""
    config = small_config()
    state = init_state(config, jax.random.key(0))
    batch, w = small_batch(), jnp.asarray([1.0, 0.5, 0.7, 0.9])
    new, m = jax.jit(lambda s, b: update_step(config, s, b, w))(state, batch)
    ref_new, ref_m, _ = jax.jit(lambda s: rt.step(
        reference.model_cfg(MODEL), rt.EXACT_OPS, s,
        (batch.obs, batch.action, batch.reward, batch.next_obs,
         batch.discount), w, jax.random.key(0)))(
        reference.init(state.actor_params, state.critic_params))
    for name in ("critic_loss", "actor_loss"):
        assert float(m[name]) == pytest.approx(float(ref_m[name]), rel=1e-5)
    np.testing.assert_allclose(np.asarray(m["td_error"]),
                               np.asarray(ref_m["td_error"]), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(m["route_counts"]),
                                  np.asarray(ref_m["route_counts"]))
    assert tree_gap(new.critic_opt_state[0].mu, ref_new["cm"]) < 1e-4
    assert tree_gap(new.critic_opt_state[0].nu, ref_new["cv"]) < 1e-4
    assert tree_gap(new.actor_opt_state[0].mu, ref_new["am"]) < 1e-4
    sub = lambda a, b: jax.tree_util.tree_map(  # noqa: E731
        lambda x, y: x - y, a, b)
    assert tree_gap(sub(new.critic_params, state.critic_params),
                    sub(ref_new["critic"], state.critic_params)) < 1e-3
    assert tree_gap(new.target_critic_params, ref_new["t_critic"]) < 1e-4
    assert tree_gap(new.actor_params, ref_new["actor"]) < 1e-4
    # the actor loss trains the actor's head alone: the torso's moments are
    # the critic loss's, and the torso is held once
    assert int(new.step) == 1


def test_fused_chunk_reports_the_load_counter_per_step_and_layer():
    config = small_config()
    state = init_state(config, jax.random.key(0))
    cap = 64
    rows = small_batch(7)
    tile = lambda x: jnp.tile(x, (cap // B,) + (1,) * (x.ndim - 1))  # noqa
    storage = TransitionBatch(*[tile(x) for x in rows])
    trees = dper.set_leaves_jitted(dper.init(cap), jnp.arange(cap),
                                   jnp.ones((cap,)))
    fn = make_fused_chunk(config, k=2, batch_size=B, donate=False)
    _state, _trees, m = fn(state, trees, storage, jnp.int32(cap))
    counts = np.asarray(m["route_counts"])
    assert counts.shape == (2, 4, 8) and counts.dtype == np.int32
    assert np.all(counts.sum(axis=-1) == B * 32 * 2)


# -- the share ----------------------------------------------------------------
def _layer_inputs(bias=None, n=8):
    """One expert layer's whole parameters (``n`` experts) and a normed
    input; ``bias`` leans the router towards the first two experts."""
    k = jax.random.split(jax.random.key(11), 5)
    fan = lambda key, shape, n: jax.random.normal(key, shape) / math.sqrt(n)  # noqa
    p = {"router": {"kernel": fan(k[0], (64, n), 64)},
         "gate": {"kernel": fan(k[1], (n, 64, 32), 64)},
         "up": {"kernel": fan(k[2], (n, 64, 32), 64)},
         "down": {"kernel": fan(k[3], (n, 32, 64), 32)}}
    h = jax.random.normal(k[4], (32, 64))
    if bias is not None:
        # every token's largest two logits are experts 0 and 1: h gets a
        # constant column the router reads with weight `bias` for those two
        h = h.at[:, 0].set(1.0)
        lean = jnp.zeros((64, n)).at[0, :2].set(bias)
        p["router"] = {"kernel": p["router"]["kernel"].at[0].set(0.0) + lean}
    return p, h


# the hybrid model's router (models/torso.route, "sigmoid" with a routing
# bias) and what every chip of it computes alike, a dense feed-forward
LFM2 = dict(router_scores="sigmoid", use_expert_bias=True,
            bias_update_rate=1e-3, rms_norm_eps=1e-5)


# the linear-attention model's expert layer: 32 experts, three a token, and
# what every chip of it computes alike, a shared expert under a sigmoid gate
QWEN3NEXT = dict(num_experts=32, num_experts_per_tok=3,
                 shared_expert_intermediate_size=32, rms_norm_eps=1e-6)


@pytest.mark.parametrize("shares, router", [
    (4, "softmax"), (8, "softmax"), (4, "sigmoid"), (8, "sigmoid"),
    (32, "shared")], ids=lambda v: {4: "four", 8: "eight",
                                    32: "thirtytwo"}.get(v, v))
@pytest.mark.parametrize("bias", [None, 50.0], ids=["seeded", "biased"])
def test_the_four_shares_add_up_to_the_uncut_layer(bias, shares, router):
    """Each share routes over all 8 experts and computes its own (2 of four
    shares, as ``humanoid-mellum2-ep4`` and ``humanoid-lfm2-ep4`` stand for;
    1 of eight, as ``humanoid-keye2-ep8``); summed they are the reference's
    whole layer, and the assignments the shares computed are all of them,
    even when two experts get every token. ``sigmoid``: LFM2's router with
    its selection bias, and the dense feed-forward that every chip computes
    alike counted once. ``shared``: 1 of 32 experts a share, three a token,
    as ``humanoid-qwen3next-ep32`` stands for, and the gated shared expert
    that ``expert_share`` adds on every chip counted once."""
    n, top = (32, 3) if router == "shared" else (8, 2)
    p, h = _layer_inputs(bias, n)
    extra = {}  # leaves every share holds whole
    if router == "sigmoid":
        from benchmark import reference_hybrid as rh

        k = jax.random.split(jax.random.key(12), 4)
        p["router"]["bias"] = 0.05 * jax.random.normal(k[0], (8,))
        if bias is not None:  # saturated scores tie: the bias decides
            p["router"]["bias"] = p["router"]["bias"].at[:2].add(10.0)
        dense = {name: {"kernel": jax.random.normal(k[i], shape) / 8}
                 for i, (name, shape) in enumerate(
                     (("w1", (64, 96)), ("w3", (64, 96)), ("w2", (96, 64))),
                     1)}
        over, block = LFM2, {**SMALL, **LFM2}
        w, e, counts, swapped = rh.route(block, h, p["router"])
        alike = rh.dense_ff(rh.EXACT_OPS, dense, h)
    elif router == "shared":
        from benchmark import reference_linear as rl

        k = jax.random.split(jax.random.key(13), 4)
        extra = {name: {"kernel": jax.random.normal(k[i], shape) / 8}
                 for i, (name, shape) in enumerate((
                     ("shared_gate", (64, 32)), ("shared_up", (64, 32)),
                     ("shared_down", (32, 64)),
                     ("shared_expert_gate", (64, 1))))}
        over, block = QWEN3NEXT, {**SMALL, **QWEN3NEXT}
        w, e, counts = rl.route(block, h, p["router"]["kernel"])
        alike, gate = rl.shared_expert(rl.EXACT_OPS, extra, h)
    else:
        over, block = {}, SMALL
        w, e, counts = rt.route(SMALL, h, p["router"]["kernel"])
        alike = jnp.zeros_like(h)
    whole = alike + rt.experts(rt.EXACT_OPS, block, p, h, w, e, held=(0, n))
    total, computed = alike, 0  # counted once, not once a share
    for index in range(shares):
        lo, hi = partition.expert_share(n, shares, index)
        spec = small_config(experts_held=[lo, hi], **over).torso
        mine = {"router": p["router"], **extra, **{
            name: {"kernel": p[name]["kernel"][lo:hi]}
            for name in ("gate", "up", "down")}}
        out, stats = torso_lib.expert_share(spec, mine, h, jnp.float32)
        seen = stats["route_counts"]
        # every share sees the same routing over all the experts
        np.testing.assert_array_equal(np.asarray(seen), np.asarray(counts))
        if router == "sigmoid":
            assert int(stats["bias_swapped"]) == int(swapped)
        # and gives what the reference gives for its experts alone
        part = rt.experts(rt.EXACT_OPS, block, mine, h, w, e, held=(lo, hi))
        if router == "shared":  # with the shared expert, on every share
            part = part + alike
            assert float(stats["shared_gate"]) == pytest.approx(
                32 * float(gate), rel=1e-5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(part),
                                   rtol=1e-4, atol=1e-5)
        total = total + (out - alike if router == "shared" else out)
        computed += int(np.asarray(seen)[lo:hi].sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert computed == 32 * top  # N x k: no assignment lost
    if bias is not None:  # every token's first two choices; a third is free
        assert np.asarray(counts)[:2].tolist() == [32, 32]
        assert int(np.asarray(counts).sum()) == 32 * top


def _parent_route(spec, h, router):
    """``models/torso.route`` as it stood before PR 50: a full sort a token
    (``lax.top_k``) and, on the sigmoid path, a scalar gather of the
    selected scores. The new selection is held to it bit for bit."""
    k = spec.num_experts_per_tok
    logits = jnp.dot(h, router["kernel"], precision=torso_lib.HI)
    stats = {}
    if spec.router_scores == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores + router["bias"] if spec.use_expert_bias else scores
        _, e = jax.lax.top_k(biased, k)
        w = jnp.take_along_axis(scores, e, axis=-1)
        if spec.use_expert_bias:
            rivals, mine = scores[:, None, :], w[:, :, None]
            first = jnp.arange(spec.num_experts) < e[:, :, None]
            ahead = jnp.sum((rivals > mine) | ((rivals == mine) & first),
                            axis=-1)
            stats["bias_swapped"] = jnp.sum(ahead >= k, dtype=jnp.int32)
        if spec.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
        w = w * spec.routed_scaling_factor
    else:
        w, e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if spec.norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
    stats["route_counts"] = jnp.sum(
        e.reshape(-1, 1) == jnp.arange(spec.num_experts)[None], axis=0,
        dtype=jnp.int32)
    return w, e.astype(jnp.int32), stats


# (experts, a token's, scores, a routing bias, held) of the benchmark's six
# routed cells: 4, 5, 6, 7, 9, 10
ROUTED_CELLS = {
    "mellum2": (64, 8, "softmax", False, (16, 32)),
    "keye2": (128, 8, "softmax", False, (0, 16)),
    "lfm2": (32, 4, "sigmoid", True, (8, 16)),
    "qwen3next": (512, 10, "softmax", False, (496, 512)),
    "nemotronh": (128, 6, "sigmoid", True, (0, 8)),
    "trinity": (128, 8, "sigmoid", True, (4, 12)),
}


@pytest.mark.parametrize("logits", ["random", "ties"])
@pytest.mark.parametrize("cell", sorted(ROUTED_CELLS))
def test_the_selection_and_the_bookkeeping_are_the_sorts_and_scatters_bit_for_bit(
        cell, logits):
    """``route`` (k passes of max / lowest index / mask, the cotangent
    placed by a compare and a select-sum, the selected scores read by the
    same one-hot) against ``lax.top_k`` / ``take_along_axis`` at each routed
    cell's experts and selection, op by op (a fused program may sum a
    token's ``k`` weights in another order): weights, experts, both counters
    and the gradients with respect to ``h`` and the router's kernel are
    equal bit for bit, on seeded logits and on logits with exact ties (every
    expert's column twice, scores saturated to exactly 1 and 0: the lower
    index wins). ``_places`` is the scattered inverse of the stable sort on
    the held assignments and past the buffer on the others."""
    n_exp, k, scores, biased, (lo, hi) = ROUTED_CELLS[cell]
    spec = small_config(
        num_experts=n_exp, num_experts_per_tok=k, router_scores=scores,
        use_expert_bias=biased, experts_held=[lo, hi],
        routed_scaling_factor=2.5 if biased else 1.0).torso
    t_len, d = 96, 64
    keys = jax.random.split(jax.random.key(n_exp + k), 4)
    h = jax.random.normal(keys[0], (t_len, d))
    kernel = jax.random.normal(keys[1], (d, n_exp)) / math.sqrt(d)
    bias = 0.05 * jax.random.normal(keys[2], (n_exp,))
    if logits == "ties":
        kernel = 40.0 * jnp.repeat(kernel[:, ::2], 2, axis=1)
        bias = jnp.repeat(bias[::2], 2)
    router = {"kernel": kernel, **({"bias": bias} if biased else {})}
    weigh = jax.random.normal(keys[3], (t_len, k))

    def run(route):
        def loss(h, kernel):
            w, e, stats = route(spec, h, {**router, "kernel": kernel})
            return jnp.sum(w * weigh), (w, e, stats)
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            h, kernel)

    (_, (w, e, stats)), grads = run(torso_lib.route)
    (_, (w0, e0, stats0)), grads0 = run(_parent_route)
    if logits == "ties":  # a tie at the selection's edge, somewhere
        logit = jnp.dot(h, kernel, precision=torso_lib.HI)
        assert bool(jnp.any(logit[:, ::2] == logit[:, 1::2]))
    np.testing.assert_array_equal(e, e0)
    np.testing.assert_array_equal(w, w0)
    assert sorted(stats) == sorted(stats0)
    for name in stats:
        np.testing.assert_array_equal(stats[name], stats0[name])
    for g, g0 in zip(grads, grads0):
        assert float(jnp.max(jnp.abs(g0))) > 0
        np.testing.assert_array_equal(g, g0)

    every = t_len * k
    order = jnp.argsort(jnp.mod(e.reshape(-1) - lo, n_exp), stable=True)
    scattered = np.asarray(jnp.zeros_like(order).at[order].set(
        jnp.arange(every, dtype=order.dtype)))
    inv = np.asarray(torso_lib._places(e, lo, stats["route_counts"][lo:hi]))
    here = np.asarray((e >= lo) & (e < hi)).reshape(-1)
    assert here.any() and not here.all()
    np.testing.assert_array_equal(inv[here], scattered[here])
    assert (inv[~here] >= every).all()


def test_expert_share_names_a_contiguous_range():
    assert [partition.expert_share(64, 4, i) for i in range(4)] == [
        (0, 16), (16, 32), (32, 48), (48, 64)]
    assert [partition.expert_share(128, 8, i) for i in (0, 7)] == [
        (0, 16), (112, 128)]
    with pytest.raises(ValueError):
        partition.expert_share(64, 5, 0)


def test_rows_of_absent_experts_may_hold_anything(monkeypatch):
    """On the chip the grouped product leaves the rows past its groups as
    the buffer held them, in its output and in its input gradient; the CPU
    writes zeros there and hides it. With NaN written to those rows, the
    share's output and every gradient stay what they were."""
    real = jax.lax.ragged_dot

    def rows_past(sizes, n):
        return (jnp.arange(n) >= jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def poisoned(x, w, sizes):
        return jnp.where(rows_past(sizes, x.shape[0]), jnp.nan,
                         real(x, w, sizes))

    def fwd(x, w, sizes):
        return poisoned(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        _, back = jax.vjp(lambda x, w: real(x, w, sizes), x, w)
        # the kernel reads group rows only: what other rows of g hold
        # must not matter, and its dx leaves them as they were
        dx, dw = back(jnp.where(rows_past(sizes, x.shape[0]), 0.0, g))
        return jnp.where(rows_past(sizes, x.shape[0]), jnp.nan, dx), dw, None

    poisoned.defvjp(fwd, bwd)
    p, h = _layer_inputs()
    spec = small_config(experts_held=[2, 4]).torso
    mine = {"router": p["router"], **{
        name: {"kernel": p[name]["kernel"][2:4]}
        for name in ("gate", "up", "down")}}

    def loss(mine, h):
        out, _counts = torso_lib.expert_share(spec, mine, h, jnp.float32)
        return jnp.sum(jnp.square(out))

    want = jax.value_and_grad(loss, argnums=(0, 1))(mine, h)
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda x, w, sizes, **_kw:
                        poisoned(x, w, sizes))
    got = jax.value_and_grad(loss, argnums=(0, 1))(mine, h)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("bias", [None, 50.0], ids=["usual", "every"])
def test_the_usual_buffer_and_the_every_assignment_buffer_agree(bias):
    """512 tokens, 2 of 8 experts held: an even load is 256 rows, the usual
    buffer 512, every assignment 1,024. Seeded routing runs the usual
    buffer; a router that sends every token to the two held experts needs
    all 1,024 rows and gets them: no assignment is dropped either way."""
    spec = torso_lib.TorsoSpec.from_dict(
        {**SMALL, "tokens": 512, "experts_held": [0, 2]})
    assert torso_lib.even_load_rows(spec, 512) == 512
    p, _ = _layer_inputs(bias)
    h = jax.random.normal(jax.random.key(5), (512, 64))
    if bias is not None:
        h = h.at[:, 0].set(1.0)
    mine = {"router": p["router"], **{
        name: {"kernel": p[name]["kernel"][0:2]}
        for name in ("gate", "up", "down")}}
    w, e, counts = rt.route(SMALL, h, p["router"]["kernel"])
    held = int(np.asarray(counts)[:2].sum())
    assert (held == 1024) if bias is not None else (held <= 512)

    def loss(fn):
        return lambda mine, h: jnp.sum(jnp.square(fn(mine, h)))

    got = jax.value_and_grad(loss(lambda mine, h: torso_lib.expert_share(
        spec, mine, h, jnp.float32)[0]), argnums=(0, 1))(mine, h)
    want = jax.value_and_grad(loss(lambda mine, h: rt.experts(
        rt.EXACT_OPS, SMALL, mine, h, *rt.route(
            SMALL, h, mine["router"]["kernel"])[:2], held=(0, 2))),
        argnums=(0, 1))(mine, h)
    for g, w_ in zip(jax.tree_util.tree_leaves(got),
                     jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w_), rtol=2e-3,
                                   atol=2e-4)


def test_the_benchmark_cells_usual_buffer_is_one_and_a_half_even_loads():
    with open(os.path.join(
            REPO, "benchmark/configs/humanoid-mellum2-ep4.json")) as f:
        spec = D4PGConfig(**json.load(f)["model"]).torso
    assert torso_lib.even_load_rows(spec, 4096) == 12288  # 1.5 x 8,192


def test_megablox_grouped_product_and_its_gradients_match_ragged_dot():
    """The Pallas kernels in interpret mode against ``jax.lax.ragged_dot``
    on the rows the groups cover (the others are nobody's)."""
    from d4pg_tpu.ops import grouped

    k = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(k[0], (512, 256))
    w = jax.random.normal(k[1], (4, 256, 128)) / 16
    ct = jax.random.normal(k[2], (512, 128))
    sizes = jnp.asarray([70, 3, 100, 50], jnp.int32)
    mine = (jnp.arange(512) < 223)[:, None]

    def run(impl, **kw):
        def f(x, w):
            x = jnp.where(mine, x, 0.0)
            y = grouped.grouped_matmul(x, w, sizes, impl=impl, **kw)
            return jnp.where(mine, y, 0.0)
        out, back = jax.vjp(f, x, w)
        return (out,) + back(ct)

    for g, want in zip(run("megablox", interpret=True), run("ragged")):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)
    # tilings the v5e measurements chose (module docstring)
    assert grouped._tiling(2304, 896, grouped.TILE_ELEMS) == (256, 2304, 896)
    assert grouped._tiling(896, 2304, grouped.TILE_ELEMS) == (256, 896, 2304)
    assert grouped._tiling(2304, 896, grouped.TGMM_TILE_ELEMS) == (
        256, 768, 896)
    assert grouped._tiling(896, 2304, grouped.TGMM_TILE_ELEMS) == (
        256, 896, 768)


# -- the entry point, acting, the env ----------------------------------------
def test_train_flag_names_the_torso_from_a_block_or_a_configuration_file(
        tmp_path):
    from d4pg_tpu.config import parse_args

    block = tmp_path / "block.json"
    block.write_text(json.dumps(SMALL))
    whole = tmp_path / "config.json"
    whole.write_text(json.dumps({"model": {"obs_dim": 32, "torso": SMALL}}))
    for path in (block, whole):
        cfg = parse_args(["--env", "point", "--torso", str(path)])
        config = cfg.learner_config(32, 3)
        assert config.torso == small_config().torso
    assert parse_args(["--env", "point"]).learner_config(4, 2).torso is None


def test_history_stacks_whole_steps_of_observation_and_action():
    from d4pg_tpu.envs.fake import PointMassEnv
    from d4pg_tpu.envs.wrappers import History

    inner = PointMassEnv(horizon=10, seed=0)
    obs_dim = int(np.prod(inner.observation_space.shape))
    act_dim = int(np.prod(inner.action_space.shape))
    step = obs_dim + act_dim
    env = History(PointMassEnv(horizon=10, seed=0), 3 * step + 2)
    first, _ = env.reset(seed=0)
    assert first.shape == (3 * step + 2,) and first.dtype == np.float32
    # three copies of the first step (zero action), then zero padding
    np.testing.assert_array_equal(first[:step], first[step:2 * step])
    np.testing.assert_array_equal(first[obs_dim:step], 0.0)
    np.testing.assert_array_equal(first[3 * step:], 0.0)
    action = np.full((act_dim,), 0.5, np.float32)
    second, *_ = env.step(action)
    np.testing.assert_array_equal(second[:2 * step], first[step:3 * step])
    np.testing.assert_array_equal(second[2 * step + obs_dim:3 * step], action)
    with pytest.raises(ValueError, match="no whole step"):
        History(PointMassEnv(horizon=10, seed=0), step - 1)


def test_acting_reads_the_actors_head_and_the_critics_torso():
    from d4pg_tpu.learner import act_deterministic, policy_params

    config = small_config()
    state = init_state(config, jax.random.key(4))
    obs = small_batch().obs
    params = policy_params(config, state)
    assert set(params["params"]) == {"actor", "torso"}
    latent, _ = config.build_critic().latent(state.critic_params, obs)
    want = config.build_actor().apply(state.actor_params, latent)
    got = act_deterministic(config, params, obs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    # without a torso the published tree is the actor's, as it was
    plain = D4PGConfig(obs_dim=5, act_dim=2, hidden=(8, 8))
    plain_state = init_state(plain, jax.random.key(0))
    assert policy_params(plain, plain_state) is plain_state.actor_params


def test_a_torso_with_a_mesh_is_refused():
    from d4pg_tpu.parallel.data_parallel import check_mesh_compatible

    with pytest.raises(ValueError, match="one device"):
        check_mesh_compatible(small_config())
