"""The sparse-attention layer type (``keye2``: ``models/torso.py`` over
``ops/sparse_attention.py``) at a small size on the CPU against the plain
reference (``benchmark/reference_sparse.py``): the selected sets themselves,
the forward pass, both losses, the whole gradient step; the selection's
exactness under ties and at every block size; which loss reaches which
parameter; full causal attention where there is nothing to discard; the
kernel's dynamic-mask form in interpret mode; the normal path through
``train.main``. Sizes: hidden 64, 4 query heads on 2 key/value heads of 16,
2 index heads of 8, top 16 of 64 tokens (four times ``topk``), 8 experts
top-2 of width 32, two layers."""

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference, reference_sparse as rs
from d4pg_tpu.learner import D4PGConfig, init_state
from d4pg_tpu.learner.fused import make_fused_chunk
from d4pg_tpu.learner.update import update_step
from d4pg_tpu.models import torso as torso_lib
from d4pg_tpu.ops import attention as attn_ops
from d4pg_tpu.ops import sparse_attention as sparse
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SA = dict(indexer_head_dim=8, indexer_num_heads=2, indexer_num_kv_heads=1,
          kv_chunk_size=8, q_chunk_size=8, topk=16)
ROPE = {"sparse_attention": {"rope_type": "default", "rope_theta": 10000000,
                             "mrope_section": [2, 3, 3]}}
SMALL = dict(
    name="keye2", tokens=64, vocab_rows=64, bins=16, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    layer_types=["sparse_attention", "sparse_attention"], num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, experts_held=[2, 6],
    qk_norm=True, sa_config=SA, rope_parameters=ROPE)
MODEL = dict(obs_dim=64, act_dim=3, hidden=(32, 32, 32), n_atoms=11,
             v_min=0.0, v_max=10.0, torso=SMALL)
B = 2
INDEXER = ("index_q", "index_k", "index_k_norm", "index_w")


def small_config(**torso_over):
    torso = {**SMALL, **torso_over}
    return D4PGConfig(**{**MODEL, "obs_dim": torso["tokens"], "torso": torso})


def small_batch(seed=1, tokens=64):
    k = jax.random.split(jax.random.key(seed), 4)
    return TransitionBatch(
        obs=3.0 * jax.random.normal(k[0], (B, tokens)),
        action=jax.random.uniform(k[1], (B, 3), minval=-1, maxval=1),
        reward=jax.random.normal(k[2], (B,)),
        next_obs=jax.random.normal(k[3], (B, tokens)),
        done=jnp.zeros((B,)), discount=jnp.full((B,), 0.99))


def seeded_state(config, seed=0):
    """``init_state`` with the norms' gains and the LayerNorm's bias moved
    off 1 and 0, so that a test sees them."""
    state = init_state(config, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 1000))

    def move(path, x):
        name = str(getattr(path[-1], "key", path[-1]))
        if name in ("scale", "bias"):
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


# -- the seam -----------------------------------------------------------------
def test_spec_takes_the_layer_type_and_its_sizes_as_data():
    config = small_config()
    spec = config.torso
    assert spec.sa["topk"] == 16 and spec.qk_norm
    assert hash(config) == hash(small_config())
    assert type(config.build_critic().torso) is torso_lib.TORSOS["mellum2"]
    layer = init_state(config, jax.random.key(0)).critic_params[
        "params"]["torso"]["layer_0"]
    assert layer["index_q"]["kernel"].shape == (64, 16)
    assert layer["index_k"]["kernel"].shape == (64, 8)
    assert layer["index_w"]["kernel"].shape == (64, 2)
    assert set(layer["index_k_norm"]) == {"scale", "bias"}
    assert layer["q_norm"]["scale"].shape == (16,)
    with pytest.raises(ValueError, match="sa_config"):
        small_config(sa_config=None)
    with pytest.raises(ValueError, match="sa_config"):
        small_config(sa_config={**SA, "window": 3})
    with pytest.raises(ValueError, match="unknown layer types"):
        small_config(layer_types=["latent_attention"])
    with pytest.raises(ValueError, match="do not divide"):
        small_config(sa_config={**SA, "q_chunk_size": 24})
    # a mellum layer beside a sparse one is one torso
    mixed = small_config(
        layer_types=["full_attention", "sparse_attention"],
        rope_parameters={**ROPE, "full_attention": ROPE["sparse_attention"]})
    layers = init_state(mixed, jax.random.key(0)).critic_params[
        "params"]["torso"]
    assert "index_q" in layers["layer_1"] and "index_q" not in layers[
        "layer_0"]


def test_mrope_with_three_equal_streams_is_one_dimensional_rope():
    for d in (16, 128):
        rope = {"rope_theta": 1e7, "rope_type": "default",
                "mrope_section": [2, 3, 3] if d == 16 else [16, 24, 24]}
        got = rs.angles(rope, d, 40)
        cos, sin = torso_lib.rope_tables(rope, d, 40)
        np.testing.assert_allclose(np.cos(np.asarray(got)), np.asarray(cos),
                                   atol=1e-6)
        np.testing.assert_allclose(np.sin(np.asarray(got)), np.asarray(sin),
                                   atol=1e-6)
    # the sections deal the frequencies: 16 to time, 24 each to the others
    x = jax.random.normal(jax.random.key(0), (40, 2, 16))
    cos, sin = torso_lib.rope_tables(ROPE["sparse_attention"], 16, 40)
    np.testing.assert_allclose(
        np.asarray(rs.rotate(x, rs.angles(ROPE["sparse_attention"], 16, 40))),
        np.asarray(torso_lib.apply_rope(x.transpose(1, 0, 2), cos,
                                        sin).transpose(1, 0, 2)),
        rtol=1e-5, atol=1e-5)


# -- the selection ------------------------------------------------------------
def dense_selection(scores, topk):
    """By hand: sort each row's causal scores, ties to the lower place."""
    scores = np.asarray(scores)
    keep = np.zeros(scores.shape, bool)
    for t in range(scores.shape[0]):
        order = sorted(range(t + 1), key=lambda s: (-scores[t, s], s))
        keep[t, order[:topk]] = True
    return keep


def causal(t_len):
    return jnp.arange(t_len)[None, :] <= jnp.arange(t_len)[:, None]


@pytest.mark.parametrize("kind", ["seeded", "ties", "signs", "constant"])
def test_the_threshold_selects_exactly_the_top_k(kind):
    t_len, topk = 48, 8
    scores = jax.random.normal(jax.random.key(3), (t_len, t_len))
    if kind == "ties":  # a handful of values: most rows tie at the cut
        scores = jnp.round(scores * 2) / 2
    elif kind == "signs":  # both zeros, infinities of the mask's kind
        scores = jnp.where(scores > 0.5, 0.0, jnp.where(
            scores < -0.5, -0.0, scores)).at[:, 5].set(-jnp.inf)
    elif kind == "constant":
        scores = jnp.zeros_like(scores)
    want = dense_selection(scores, topk)
    got = np.asarray(sparse.select(scores, causal(t_len), topk))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        np.asarray(rs.selection(scores, 0, topk)), want)
    assert np.all(got.sum(axis=1) == np.minimum(np.arange(t_len) + 1, topk))


def index_inputs(t_len=64, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (t_len, 2, 8)),
            jax.random.normal(k[1], (t_len, 8)),
            jax.random.normal(k[2], (t_len, 2)) / 4)


@pytest.mark.parametrize("q_chunk, kv_chunk", [(64, 64), (8, 8), (16, 4),
                                               (4, 32), (2, 2)])
def test_the_selection_is_the_same_at_every_block_size(q_chunk, kv_chunk):
    qi, ki, wi = index_inputs()
    want = dense_selection(sparse.index_scores(qi, ki, wi), 16)
    keep, counts = sparse.select_keys(
        qi, ki, wi, topk=16, q_chunk=q_chunk, kv_chunk=kv_chunk)
    np.testing.assert_array_equal(np.asarray(keep), want)
    np.testing.assert_array_equal(
        np.asarray(counts), want.sum(0).reshape(-1, kv_chunk).sum(-1))
    assert int(counts.sum()) == 16 * 17 // 2 + 48 * 16


def test_the_plan_groups_query_blocks_by_how_far_they_see():
    plan = sparse.block_plan(16384, 512, 512)
    assert len(plan) == sparse.GROUPS == 8
    assert [n for _first, n, _extent in plan] == [4] * 8
    assert [extent for *_rest, extent in plan] == [
        2048 * (g + 1) for g in range(8)]
    # 9/16 of the square, where the causal half is 1/2
    assert sum(n * 512 * extent for _f, n, extent in plan) == 9 * 16384 ** 2 \
        // 16
    assert sparse.block_plan(64, 8, 32) == [
        (8 * g, 1, 32 if g < 4 else 64) for g in range(8)]
    assert sparse.block_plan(16, 8, 16) == [(0, 1, 16), (8, 1, 16)]


# -- attention under the selection --------------------------------------------
def attention_inputs(t_len, seed=0, d=16):
    k = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k[0], (2, 2, t_len, d)) / math.sqrt(d),
            jax.random.normal(k[1], (2, t_len, d)),
            jax.random.normal(k[2], (2, t_len, d)),
            jax.random.normal(k[3], (2, 2, t_len, d)))


def naive_masked(q, k, v, keep):
    s = jnp.einsum("hgqd,hkd->hgqk", q, k, precision=reference.HI)
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,hkd->hgqd", p, v, precision=reference.HI)


def with_gradients(fn, q, k, v, ct):
    out, back = jax.vjp(fn, q, k, v)
    return (out,) + back(ct)


def test_blockwise_masked_attention_and_its_gradient_match_a_naive_mask():
    q, k, v, ct = attention_inputs(64)
    qi, ki, wi = index_inputs()
    keep, _ = sparse.select_keys(qi, ki, wi, topk=16, q_chunk=8, kv_chunk=8)
    got = with_gradients(lambda q, k, v: sparse.masked_attention(
        q, k, v, keep, impl="blockwise", q_chunk=8, kv_chunk=16), q, k, v, ct)
    want = with_gradients(lambda q, k, v: naive_masked(q, k, v, keep),
                          q, k, v, ct)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5)


def test_the_kernels_dynamic_mask_form_matches_the_blockwise_form():
    """The three kernels in interpret mode, one int8 tile of the mask for
    all the heads of a group, forward and backward."""
    q, k, v, ct = attention_inputs(256, seed=2, d=128)
    qi, ki, wi = index_inputs(256, seed=2)
    keep, _ = sparse.select_keys(qi, ki, wi, topk=64, q_chunk=64,
                                 kv_chunk=64)
    assert int(keep.sum()) == 64 * 65 // 2 + 192 * 64
    got = with_gradients(lambda q, k, v: sparse.splash_masked_attention(
        q, k, v, keep, interpret=True), q, k, v, ct)
    want = with_gradients(lambda q, k, v: naive_masked(q, k, v, keep),
                          q, k, v, ct)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-3)


def test_the_alignment_loss_through_the_kernels_matches_the_jnp_form():
    """The kernel form in interpret mode: every head's log-sum-exp from the
    main attention's own forward call, the heads' probabilities summed in
    ``head_mean_probs_kernel``; value and gradient against plain ``jnp``
    (``lse`` ``None``), at two block sizes (one key tile a block, and
    several)."""
    q, k, v, _ = attention_inputs(256, seed=4, d=128)
    qi, ki, wi = index_inputs(256, seed=4)
    keep, _ = sparse.select_keys(qi, ki, wi, topk=64, q_chunk=64,
                                 kv_chunk=64)
    _out, lse = sparse.splash_attention_and_lse(q, k, v, keep,
                                                interpret=True)

    def loss(lse, q_chunk, **kw):
        return jax.value_and_grad(lambda qi, ki, wi: sparse.alignment_loss(
            qi, ki, wi, keep, q, k, lse, q_chunk=q_chunk, kv_chunk=128,
            **kw), argnums=(0, 1, 2))(qi, ki, wi)

    want, want_grads = loss(None, 64)
    assert float(want) > 1.0
    for q_chunk in (128, 16):
        got, got_grads = loss(lse, q_chunk, interpret=True)
        assert float(got) == pytest.approx(float(want), rel=1e-4)
        for g, w in zip(got_grads, want_grads):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-3, atol=1e-5)
    # the main attention's operands get nothing from it
    zero = jax.grad(lambda q, k: sparse.alignment_loss(
        qi, ki, wi, keep, q, k, None, q_chunk=64, kv_chunk=64),
        argnums=(0, 1))(q, k)
    assert all(float(jnp.max(jnp.abs(z))) == 0.0 for z in zero)


# -- one forward call for the output and the loss -----------------------------
def jax_layouts(keep, group, t_len):
    """``(block sizes, keep's MaskInfo by query, by key)`` as jax's own
    dynamic-mask kernels of one key/value head and its ``group`` query
    heads take them (until PR 46 the module made these for its backward):
    ``process_dynamic_mask`` lays the mask out as int32 blocks, once for
    all the heads, every head's tables pointing at them."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sa, splash_attention_mask_info as info)

    b = min(sparse.SPLASH_BLOCK, t_len)

    def shared(process):
        laid, _ = process(keep[None], (b, b), downcast_smem_data=True,
                          head_shards=1, q_seq_shards=1)
        every = lambda a: jnp.broadcast_to(a, (group,) + a.shape[1:])  # noqa
        return laid._replace(
            data_next=every(laid.data_next), mask_next=every(laid.mask_next),
            block_mask=every(laid.block_mask),
            partial_mask_blocks=laid.partial_mask_blocks.reshape(-1, b, b))

    sizes = sa.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    return (sizes, shared(info.process_dynamic_mask),
            shared(info.process_dynamic_mask_dkv))


def residual_forward(q, k, v, keep):
    """Until PR 42 the loss's own pass: jax's kernel object asked to keep
    its residuals, ``(out, lse)``, not differentiable."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sa)

    sizes, by_query, _ = jax_layouts(keep, q.shape[1], q.shape[2])
    kernel = sa.SplashAttentionKernel(
        by_query, None, None, block_sizes=sizes, is_mqa=True,
        save_residuals=True,
        mask_value=sa.DEFAULT_MASK_VALUE, attn_logits_soft_cap=None,
        residual_checkpoint_name=None, mask_function=None, interpret=True)
    out, (lse,) = jax.vmap(kernel)(q, k, v)
    return out, lse


def jax_backward(q, k, v, out, lse, d_out, keep):
    """``(dq, dk, dv)`` by jax 0.9.0's own ``dq`` and ``dkv`` kernels in
    interpret mode (``_splash_attention_bwd``, what the module's backward
    rule called until PR 46) on ``jax_layouts``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sa)

    sizes, by_query, by_key = jax_layouts(keep, q.shape[1], q.shape[2])

    def head(q, k, v, out, lse, d_out):
        return sa._splash_attention_bwd(
            False, sa.DEFAULT_MASK_VALUE, True, sizes, None, None, None,
            True, (q, k, v, None, None, out, lse, by_query, by_key),
            d_out)[3:6]

    return jax.vmap(head)(q, k, v, out, lse, d_out)


@pytest.fixture(scope="module")
def selected():
    """256 tokens, two key/value heads of 128 with two query heads each,
    the top 64: ``(q, k, v, ct)``, the indexer's ``(qi, ki, wi)``, ``keep``
    and the two forms' ``(out, lse)``."""
    q, k, v, ct = attention_inputs(256, seed=4, d=128)
    index = index_inputs(256, seed=4)
    keep, _ = sparse.select_keys(*index, topk=64, q_chunk=64, kv_chunk=64)
    return ((q, k, v, ct), index, keep,
            sparse.splash_attention_and_lse(q, k, v, keep, interpret=True),
            residual_forward(q, k, v, keep))


def same_bits(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.dtype == b.dtype and a.shape == b.shape


@pytest.mark.parametrize("part", ["out", "dq", "dk", "dv"])
def test_the_one_call_gives_the_kernels_own_output_and_gradients(selected,
                                                                 part):
    """``splash_attention_and_lse`` differentiated (this module's three
    kernels) against jax's own kernels on the mask's int32 layouts, the
    forward that keeps its residuals and ``_splash_attention_bwd``: bit for
    bit (256 tokens are one block, so the sums' order is jax's too)."""
    (q, k, v, ct), _index, keep, (out, lse), (old_out, _old_lse) = selected
    got = with_gradients(lambda q, k, v: sparse.splash_attention_and_lse(
        q, k, v, keep, interpret=True)[0], q, k, v, ct)
    want = (old_out,) + tuple(jax_backward(q, k, v, out, lse, ct, keep))
    i = ["out", "dq", "dk", "dv"].index(part)
    same_bits(got[i], want[i])
    assert float(jnp.max(jnp.abs(got[i]))) > 0
    # the pass that keeps no lse has the same gradients
    same_bits(got[i], with_gradients(
        lambda q, k, v: sparse.splash_masked_attention(
            q, k, v, keep, interpret=True), q, k, v, ct)[i])


def test_the_one_calls_lse_is_the_residual_keeping_forwards(selected):
    (q, k, _v, _ct), _index, keep, (out, lse), (old_out, old_lse) = selected
    same_bits(lse, old_lse)
    same_bits(out, old_out)
    assert lse.shape == (2, 2, 256) and lse.dtype == jnp.float32
    s = jnp.einsum("hgqd,hkd->hgqk", q, k, precision=reference.HI)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(
            jnp.where(keep, s, -jnp.inf), axis=-1)), rtol=2e-3, atol=2e-3)
    # the attention that is not differentiated keeps no residual, same bits
    same_bits(sparse.splash_masked_attention(q, k, _v, keep, interpret=True),
              out)


@pytest.mark.parametrize("q_chunk", [128, 16])
def test_the_alignment_loss_given_that_lse_is_the_loss_of_its_own_pass(
        selected, q_chunk):
    """The loss and its three gradients with the log-sum-exp of the
    attention's own call against the same with the residual-keeping
    forward's (what ``alignment_loss`` fetched for itself until PR 42): bit
    for bit; ``q``, ``k`` and the ``lse`` get exact zeros."""
    (q, k, _v, _ct), (qi, ki, wi), keep, (_, lse), (_, old_lse) = selected

    def loss(lse):
        return jax.value_and_grad(
            lambda qi, ki, wi, q, k, lse: sparse.alignment_loss(
                qi, ki, wi, keep, q, k, lse, q_chunk=q_chunk, kv_chunk=128,
                interpret=True), argnums=(0, 1, 2, 3, 4, 5))(
                    qi, ki, wi, q, k, lse)

    got, got_grads = loss(lse)
    want, want_grads = loss(old_lse)
    assert float(got) == float(want) > 1.0
    for g, w in zip(got_grads, want_grads):
        same_bits(g, w)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in got_grads[:3])
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in got_grads[3:])


def both_losses(keep, ct, constant=True):
    """The attention's output against ``ct`` plus the alignment loss, as
    ``_attend_sparse`` puts them together."""
    def fn(q, k, v, qi, ki, wi):
        if constant:
            out, lse = sparse.splash_attention_and_lse(q, k, v, keep,
                                                       interpret=True)
        else:  # the custom_vjp itself, with nothing between it and a reader
            out, lse = sparse._splash_out_and_lse(True, keep, q, k, v)
        return (jnp.sum(out * ct) + jnp.sum(lse) * (not constant)
                + sparse.alignment_loss(qi, ki, wi, keep, q, k, lse,
                                        q_chunk=128, kv_chunk=128,
                                        interpret=True))
    return fn


def test_the_two_losses_through_one_call_are_the_two_losses_apart(selected):
    """Differentiated together through the one forward call (under a
    ``jax.checkpoint``, as the layer is): ``q, k, v`` get the attention's
    gradient alone and the indexer the loss's alone, bit for bit; the
    backward rule ran, so the cotangent that reached it on ``lse`` was a
    symbolic zero (it refuses any other)."""
    (q, k, v, ct), (qi, ki, wi), keep, (_, lse), _old = selected
    got = jax.grad(jax.checkpoint(both_losses(keep, ct)),
                   argnums=tuple(range(6)))(q, k, v, qi, ki, wi)
    attn = with_gradients(lambda q, k, v: sparse.splash_masked_attention(
        q, k, v, keep, interpret=True), q, k, v, ct)[1:]
    index = jax.grad(lambda qi, ki, wi: sparse.alignment_loss(
        qi, ki, wi, keep, q, k, lse, q_chunk=128, kv_chunk=128,
        interpret=True), argnums=(0, 1, 2))(qi, ki, wi)
    for g, w in zip(got, attn + index):
        same_bits(g, w)


def test_a_cotangent_on_the_lse_is_refused(selected):
    """Read without the stop-gradient ``splash_attention_and_lse`` puts on
    it, the log-sum-exp would need a backward the kernel does not have: the
    rule raises rather than drop it."""
    (q, k, v, ct), index, keep, _new, _old = selected
    with pytest.raises(TypeError, match="log-sum-exp is handed out as a "
                                        "constant"):
        jax.grad(both_losses(keep, ct, constant=False))(q, k, v, *index)
    # and through the public function nothing of it is asked for
    out, back = jax.vjp(lambda q, k, v: sparse.splash_attention_and_lse(
        q, k, v, keep, interpret=True), q, k, v)
    zero, one = jnp.zeros_like(out[1]), jnp.ones_like(out[1])
    for g, w in zip(back((ct, one)), back((ct, zero))):
        same_bits(g, w)


def test_attention_and_lse_is_the_plain_attention_where_no_kernel_runs():
    """``blockwise``: the output of ``masked_attention`` and no log-sum-exp
    (the loss then makes its own target in ``jnp``)."""
    q, k, v, _ = attention_inputs(64)
    keep, _ = sparse.select_keys(*index_inputs(), topk=16, q_chunk=8,
                                 kv_chunk=8)
    kw = dict(impl="blockwise", q_chunk=8, kv_chunk=16)
    out, lse = sparse.attention_and_lse(q, k, v, keep, **kw)
    assert lse is None
    same_bits(out, sparse.masked_attention(q, k, v, keep, **kw))
    with pytest.raises(ValueError, match="unknown attention impl"):
        sparse.attention_and_lse(q, k, v, keep, impl="dense", q_chunk=8,
                                 kv_chunk=16)


# -- the layer against the reference ------------------------------------------
# -- the forward kernel: one mask tile for the heads of a group ---------------
KINDS = ("topk", "empty_block", "full_block", "not_causal")
T_KERNEL = 256


@functools.lru_cache(maxsize=None)
def selection(kind):
    """``keep [256, 256]``: the top 64 of seeded index scores; the same with
    queries 128.. keeping none of keys ..127 (blocks of 128: one below the
    diagonal is empty; every query keeps itself); with those queries keeping
    all of them (that block is kept whole, as is the first 64 queries'
    causal triangle); a seeded third of ALL pairs, above the diagonal
    too; and the top 64 with keys 128.. and key 5 kept by no query (a whole
    block of keys, and one column of a kept block; every query keeps key
    0)."""
    rows = jnp.arange(T_KERNEL)[:, None]
    cols = jnp.arange(T_KERNEL)[None, :]
    if kind == "not_causal":
        return jax.random.bernoulli(jax.random.key(9), 0.3,
                                    (T_KERNEL, T_KERNEL)) | (rows == cols)
    keep, _ = sparse.select_keys(*index_inputs(T_KERNEL, seed=6), topk=64,
                                 q_chunk=64, kv_chunk=64)
    corner = (rows >= 128) & (cols < 128)
    if kind == "empty_columns":
        return (keep & (cols < 128) & (cols != 5)) | (cols == 0)
    if kind == "empty_block":
        return (keep & ~corner) | (rows == cols)
    return keep | corner if kind == "full_block" else keep


@functools.lru_cache(maxsize=None)
def grouped_inputs(group):
    k = jax.random.split(jax.random.key(10 + group), 4)
    heads, keys = (2, group, T_KERNEL, 128), (2, T_KERNEL, 128)
    return (jax.random.normal(k[0], heads) / math.sqrt(128),
            jax.random.normal(k[1], keys), jax.random.normal(k[2], keys),
            jax.random.normal(k[3], heads))


@functools.lru_cache(maxsize=None)
def references(group, kind):
    """``(out, lse)`` by the plain mask, and by jax's own forward kernel
    (``residual_forward`` in blocks of 128: the mask laid out as int32 by
    query) in interpret mode."""
    q, k, v, _ = grouped_inputs(group)
    keep = selection(kind)
    s = jnp.where(keep, jnp.einsum("hgqd,hkd->hgqk", q, k,
                                   precision=reference.HI), -jnp.inf)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sparse, "SPLASH_BLOCK", 128)
        by_jax = residual_forward(q, k, v, keep)
    return ((naive_masked(q, k, v, keep), jax.nn.logsumexp(s, axis=-1)),
            by_jax)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("block_q, block_kv", [(128, 128), (256, 128),
                                               (128, 256), (64, 128)])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_forward_kernel_is_the_masked_attention_and_its_lse(
        group, block_q, block_kv, kind):
    """``group_masked_forward`` in interpret mode, the ``group`` heads of a
    key/value head over one int8 tile of ``keep``: ``out`` and ``lse``
    against the plain mask, against ``blockwise_masked_attention`` (which
    reads the causal extent only) and against jax's forward kernel on the
    same operands, bit for bit where the blocks are jax's; at blocks that
    are square, and that cut the diagonal into unequal parts; with a block
    that keeps nothing (skipped: never fetched, never run), one kept
    whole, and a selection that is not causal."""
    q, k, v, _ = grouped_inputs(group)
    keep = selection(kind)
    table = np.asarray(sparse._block_table(
        sparse._kept_blocks(keep, block_q, block_kv)))
    visited = table == np.arange(table.shape[1])
    some = np.asarray(keep).reshape(T_KERNEL // block_q, block_q,
                                    T_KERNEL // block_kv, block_kv).any(
                                        axis=(1, 3))
    np.testing.assert_array_equal(visited, some)
    if kind == "empty_block" and block_kv == 128 and block_q <= 128:
        assert not visited[-1, 0] and table[-1, 0] == 1
    out, lse = sparse.group_masked_forward(
        q, k, v, keep, block_q=block_q, block_kv=block_kv, interpret=True)
    assert out.shape == q.shape and out.dtype == q.dtype
    assert lse.shape == q.shape[:3] and lse.dtype == jnp.float32
    (want, want_lse), (jax_out, jax_lse) = references(group, kind)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-3, atol=2e-3)
    if (block_q, block_kv) == (128, 128):
        same_bits(out, jax_out)
        same_bits(lse, jax_lse)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax_out),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(jax_lse),
                               rtol=1e-5, atol=1e-6)
    if kind != "not_causal":
        blockwise = sparse.blockwise_masked_attention(
            q, k, v, keep, q_chunk=64, kv_chunk=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(blockwise),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kind", KINDS)
def test_the_pass_that_drops_the_lse_is_the_same_forward_call(kind):
    """``splash_masked_attention`` (what ``masked_attention`` calls for
    ``impl="splash"``) is ``splash_attention_and_lse``'s output, bit for
    bit, whose ``lse`` is the plain mask's (that the undifferentiated
    program lays out no int32 mask is pinned on the compiled program,
    ``tests/test_torso_v5e_compile.py``)."""
    q, k, v, _ = grouped_inputs(2)
    keep = selection(kind)
    out, lse = sparse.splash_attention_and_lse(q, k, v, keep, interpret=True)
    same_bits(sparse.splash_masked_attention(q, k, v, keep, interpret=True),
              out)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(references(2, kind)[0][1]), rtol=2e-3,
        atol=2e-3)


def test_forward_pass_selections_and_index_loss_match_the_reference():
    config = small_config()
    params = seeded_state(config, 3).critic_params
    obs = small_batch().obs
    latent, aux = config.build_critic().latent(params, obs, train=True)
    want, counts, selected, index_loss = rs.torso(
        rs.EXACT_OPS, SMALL, params["params"]["torso"], obs)
    np.testing.assert_allclose(np.asarray(latent), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(aux["route_counts"]),
                                  np.asarray(counts))
    np.testing.assert_array_equal(np.asarray(aux["select_counts"]),
                                  np.asarray(selected))
    assert aux["select_counts"].shape == (2, 8)
    assert np.all(np.asarray(selected).sum(-1) == B * (136 + 48 * 16))
    assert float(aux["index_loss"]) == pytest.approx(float(index_loss),
                                                     rel=1e-4)
    assert float(index_loss) > 1e-3
    # the passes that are not differentiated hand up the load counter alone
    latent2, aux2 = config.build_critic().latent(params, obs)
    assert set(aux2) == {"route_counts"}
    np.testing.assert_array_equal(np.asarray(latent2), np.asarray(latent))


def test_the_selected_sets_are_the_references():
    """One layer's inputs through the program's stage 1 and through the
    reference's dense ``top_k`` mask: the same set for every query."""
    config = small_config()
    p = seeded_state(config, 5).critic_params["params"]["torso"]["layer_0"]
    spec = config.torso
    x = jax.random.normal(jax.random.key(9), (64, 64))
    h = torso_lib.rms_norm(x, p["attn_norm"]["scale"], 1e-6)
    cos, sin = torso_lib.rope_tables(ROPE["sparse_attention"], 8, 64)
    qi = torso_lib.apply_rope(jnp.dot(h, p["index_q"]["kernel"]).reshape(
        64, 2, 8).transpose(1, 0, 2), cos, sin).transpose(1, 0, 2)
    ki = torso_lib.apply_rope(torso_lib.layer_norm(
        jnp.dot(h, p["index_k"]["kernel"]), p["index_k_norm"], 1e-6), cos,
        sin)
    wi = jnp.dot(h, p["index_w"]["kernel"]) / 4.0
    keep, _ = sparse.select_keys(qi, ki, wi, topk=16, q_chunk=8, kv_chunk=8)
    small = rs.angles(ROPE["sparse_attention"], 8, 64)
    scores = jnp.sum(jax.nn.relu(jnp.einsum(
        "qhd,kd->qhk", rs.rotate(jnp.dot(h, p["index_q"]["kernel"]).reshape(
            64, 2, 8), small),
        rs.rotate(rs.layer_norm(jnp.dot(h, p["index_k"]["kernel"]),
                                p["index_k_norm"], 1e-6)[:, None], small)[
            :, 0], precision=reference.HI)) * wi[:, :, None], axis=1)
    np.testing.assert_array_equal(np.asarray(keep),
                                  np.asarray(rs.selection(scores, 0, 16)))
    assert spec.sa["topk"] == 16


def test_whole_step_matches_the_reference():
    config = small_config()
    state = seeded_state(config)
    batch, w = small_batch(), jnp.asarray([1.0, 0.5])
    new, m = jax.jit(lambda s, b: update_step(config, s, b, w))(state, batch)
    ref_new, ref_m, _ = jax.jit(lambda s: rs.step(
        reference.model_cfg(MODEL), rs.EXACT_OPS, s,
        (batch.obs, batch.action, batch.reward, batch.next_obs,
         batch.discount), w, jax.random.key(0)))(
        reference.init(state.actor_params, state.critic_params))
    for name in ("critic_loss", "actor_loss", "index_loss"):
        assert float(m[name]) == pytest.approx(float(ref_m[name]), rel=1e-4)
    np.testing.assert_allclose(np.asarray(m["td_error"]),
                               np.asarray(ref_m["td_error"]), rtol=1e-4)
    for name in ("route_counts", "select_counts"):
        np.testing.assert_array_equal(np.asarray(m[name]),
                                      np.asarray(ref_m[name]))
    assert tree_gap(new.critic_opt_state[0].mu, ref_new["cm"]) < 1e-3
    assert tree_gap(new.critic_opt_state[0].nu, ref_new["cv"]) < 1e-3
    assert tree_gap(new.actor_opt_state[0].mu, ref_new["am"]) < 1e-3
    sub = lambda a, b: jax.tree_util.tree_map(  # noqa: E731
        lambda x, y: x - y, a, b)
    assert tree_gap(sub(new.critic_params, state.critic_params),
                    sub(ref_new["critic"], state.critic_params)) < 2e-3
    assert tree_gap(new.target_critic_params, ref_new["t_critic"]) < 1e-4
    # every indexer leaf moved: the alignment loss trains it
    mu = new.critic_opt_state[0].mu["params"]["torso"]["layer_1"]
    for name in INDEXER:
        assert all(float(jnp.max(jnp.abs(x))) > 0
                   for x in jax.tree_util.tree_leaves(mu[name]))


def test_each_loss_reaches_its_own_parameters_and_no_others():
    """The critic loss's gradient is exactly zero on the indexer; the
    alignment loss's exactly zero on everything else."""
    config = small_config()
    state = seeded_state(config, 2)
    critic, batch = config.build_critic(), small_batch(4)

    def critic_loss(p):
        z, _ = critic.latent(p, batch.obs, train=True)
        return jnp.sum(jnp.square(critic.of_latent(p, z, batch.action)))

    def index_loss(p):
        return critic.latent(p, batch.obs, train=True)[1]["index_loss"]

    by_critic = jax.grad(critic_loss)(state.critic_params)["params"]
    by_index = jax.grad(index_loss)(state.critic_params)["params"]
    amax = lambda t: max(float(jnp.max(jnp.abs(x)))  # noqa: E731
                         for x in jax.tree_util.tree_leaves(t))
    assert amax(by_index["critic"]) == 0.0
    for tree, is_zero in ((by_critic, True), (by_index, False)):
        torso = tree["torso"]
        for name, leaf in torso.items():
            if not name.startswith("layer_"):
                assert (amax(leaf) == 0.0) == (not is_zero), name
                continue
            for part, sub in leaf.items():
                indexer = part in INDEXER
                if indexer == is_zero:
                    assert amax(sub) == 0.0, (name, part)
                else:
                    assert amax(sub) > 0.0, (name, part)


def test_with_no_key_to_discard_the_layer_is_full_causal_attention():
    """``tokens <= topk``: every query keeps every earlier position and the
    layer is the dense layer through ``causal_attention``."""
    config = small_config(tokens=16, sa_config={**SA, "q_chunk_size": 4,
                                                "kv_chunk_size": 4})
    torso = config.build_critic().torso
    p = seeded_state(config, 7).critic_params["params"]["torso"]["layer_0"]
    x = jax.random.normal(jax.random.key(1), (16, 64))
    got, (counts, _loss) = torso._attend_sparse(p, x, True)
    h = torso_lib.rms_norm(x, p["attn_norm"]["scale"], 1e-6)
    q, k, v, _gate = torso._qkv(p, h, "sparse_attention")
    a = attn_ops.causal_attention(q[None], k[None], v[None], window=None,
                                  impl="blockwise")[0]
    want = x + jnp.dot(a.transpose(2, 0, 1, 3).reshape(16, -1),
                       p["o"]["kernel"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)
    assert int(counts.sum()) == 16 * 17 // 2


def test_the_expert_layer_a_part_of_the_sequence_at_a_time_is_the_whole(
        monkeypatch):
    """A sequence longer than ``EXPERT_TOKENS`` goes through the expert
    layer in parts (the buffers are a part's): the same output, counts and
    gradients as in one piece."""
    config = small_config()
    torso = config.build_critic().torso
    p = seeded_state(config, 6).critic_params["params"]["torso"]["layer_0"]
    h = jax.random.normal(jax.random.key(2), (64, 64))

    def run():
        def loss(p, h):
            out, stats = torso._experts(p, h)
            return jnp.sum(jnp.square(out)), stats["route_counts"]
        (value, counts), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(p, h)
        return value, counts, grads

    assert torso_lib.EXPERT_TOKENS == 4096  # cell 4's sequence: one piece
    whole = run()
    monkeypatch.setattr(torso_lib, "EXPERT_TOKENS", 16)
    parts = run()
    assert float(parts[0]) == pytest.approx(float(whole[0]), rel=1e-5)
    np.testing.assert_array_equal(np.asarray(parts[1]), np.asarray(whole[1]))
    for g, w in zip(jax.tree_util.tree_leaves(parts[2]),
                    jax.tree_util.tree_leaves(whole[2])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-5)
    # and the reference's experts a block of tokens at a time likewise
    w_, e, _ = rs.rt.route(SMALL, h, p["router"]["kernel"])
    monkeypatch.setattr(rs, "EXPERT_BLOCK", 16)
    np.testing.assert_allclose(
        np.asarray(rs.experts(rs.EXACT_OPS, SMALL, p, h, w_, e)),
        np.asarray(rs.rt.experts(rs.EXACT_OPS, SMALL, p, h, w_, e)),
        rtol=1e-5, atol=1e-6)


# -- the fused chunk and the entry point --------------------------------------
def test_fused_chunk_reports_the_selection_counter_and_the_index_loss():
    config = small_config()
    state = init_state(config, jax.random.key(0))
    cap = 16
    rows = small_batch(7)
    tile = lambda x: jnp.tile(x, (cap // B,) + (1,) * (x.ndim - 1))  # noqa
    storage = TransitionBatch(*[tile(x) for x in rows])
    trees = dper.set_leaves_jitted(dper.init(cap), jnp.arange(cap),
                                   jnp.ones((cap,)))
    fn = make_fused_chunk(config, k=2, batch_size=B, donate=False)
    assert fn.lower(state, trees, storage, jnp.int32(cap)).as_text().split(
        "\n", 1)[0].startswith("module @jit_fn")
    _state, _trees, m = fn(state, trees, storage, jnp.int32(cap))
    selected = np.asarray(m["select_counts"])
    assert selected.shape == (2, 2, 8) and selected.dtype == np.int32
    assert np.all(selected.sum(axis=-1) == B * (136 + 48 * 16))
    assert m["index_loss"].shape == (2,)
    assert np.all(np.isfinite(np.asarray(m["index_loss"])))
    assert m["route_counts"].shape == (2, 2, 8)


def test_train_main_runs_the_benchmark_files_rehearsal_torso(tmp_path):
    """``train.main --torso <the benchmark's configuration file>`` at its
    rehearsal sizes: cycles complete through ``FusedLoop`` with finite
    losses."""
    from benchmark import cellbuild
    from d4pg_tpu import train

    cfg = cellbuild.load_config("humanoid-keye2-ep8", True)
    block = tmp_path / "torso.json"
    block.write_text(json.dumps(cfg["model"]["torso"]))
    out = train.main([
        "--platform", "cpu", "--env", "point", "--torso", str(block),
        "--p_replay", "1", "--fused_replay", "on", "--replay_storage",
        "device", "--bsize", "2", "--rmsize", "256", "--warmup", "32",
        "--n_eps", "1", "--n_cycles", "2", "--train_steps_per_cycle", "2",
        "--updates_per_dispatch", "1", "--max_steps", "10",
        "--eval_trials", "1", "--log_dir", str(tmp_path / "runs")])
    assert out["learner_step"] >= 4
    assert np.isfinite(out["critic_loss"]) and np.isfinite(out["actor_loss"])
    assert out["plan"]["fused"] is True and out["plan"]["K"] == 1
