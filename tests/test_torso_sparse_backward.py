"""The backward of attention under a run-time selection, the repo's own
``group_masked_dq`` and ``group_masked_dkv`` (``ops/sparse_attention.py``, PR
46), in interpret mode against jax 0.9.0's backward kernels on int32 layouts
the tests build from jax's public ``process_dynamic_mask*``, against the
plain mask and against the blockwise form. A file of its own beside
``test_torso_sparse.py`` (whose inputs and references these are) so that a
run that deals files to workers does not put both on one."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.ops import sparse_attention as sparse
from test_torso_sparse import (KINDS, grouped_inputs, jax_backward,
                               naive_masked, same_bits, selection,
                               with_gradients)

BACKWARD_KINDS = KINDS + ("empty_columns",)


@functools.lru_cache(maxsize=None)
def backward_references(group, kind):
    """``(out, lse, di)`` of this module's forward in blocks of 128, and
    ``(dq, dk, dv)`` by jax's own backward kernels on them (blocks of 128,
    the mask laid out as int32 by query and by key) and by the plain
    mask."""
    q, k, v, ct = grouped_inputs(group)
    keep = selection(kind)
    out, lse = sparse.group_masked_forward(q, k, v, keep, block_q=128,
                                           block_kv=128, interpret=True)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(sparse, "SPLASH_BLOCK", 128)
        by_jax = jax_backward(q, k, v, out, lse, ct, keep)
    naive = with_gradients(lambda q, k, v: naive_masked(q, k, v, keep),
                           q, k, v, ct)[1:]
    return (out, lse, jnp.einsum("hgsd,hgsd->hgs", out, ct)), by_jax, naive


@pytest.mark.parametrize("kind", BACKWARD_KINDS)
@pytest.mark.parametrize("block_q, block_kv", [(128, 128), (256, 128),
                                               (128, 256)])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_backward_kernels_are_jaxs_backward(group, block_q, block_kv,
                                                kind):
    """``group_masked_dq`` and ``group_masked_dkv`` in interpret mode, the
    ``group`` heads of a key/value head over one int8 tile of ``keep``
    (``dkv`` turns it in the kernel), against jax's ``_splash_attention_bwd`` on
    its own int32 layouts and against the plain mask's gradients; at blocks
    that are square and that cut the diagonal into unequal parts; with a
    block that keeps nothing (never fetched, never run), one kept whole, a
    selection that is not causal, and keys that no query keeps (their
    ``dk`` and ``dv`` are exact zeros: a block of such keys runs once, on a
    tile that masks everything). ``dq`` sums a query's blocks of keys in
    jax's order: the same bits where the blocks of keys are jax's. ``dk``
    and ``dv`` sum a block's heads and then the next block's where jax sums
    a head's blocks and then the next head's: the same terms, float32."""
    q, k, v, ct = grouped_inputs(group)
    keep = selection(kind)
    (_out, lse, di), by_jax, naive = backward_references(group, kind)
    kept = keep.astype(jnp.int8)
    some = sparse._kept_blocks(keep, block_q, block_kv)
    by_columns = np.asarray(sparse._block_table(some.T))
    # a step runs where its block keeps a pair (a block of keys that no
    # query keeps runs its first step, on a tile that masks everything)
    any_kept = np.asarray(some.T).any(axis=1)
    np.testing.assert_array_equal(
        (by_columns == np.arange(by_columns.shape[1]))[any_kept],
        np.asarray(some.T)[any_kept])
    blocks = dict(block_q=block_q, block_kv=block_kv, interpret=True)
    dq = sparse.group_masked_dq(q, k, v, kept, sparse._block_table(some),
                                lse, di, ct, **blocks)
    dk, dv = sparse.group_masked_dkv(q, k, v, kept, by_columns, lse, di,
                                     ct, **blocks)
    for got, like in zip((dq, dk, dv), (q, k, v)):
        assert got.shape == like.shape and got.dtype == like.dtype
    if block_kv == 128:
        same_bits(dq, by_jax[0])
    for got, want, plain in zip((dq, dk, dv), by_jax, naive):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   rtol=2e-3, atol=2e-3)
        assert float(jnp.max(jnp.abs(got))) > 0
    if kind == "empty_columns":
        for got in (dk, dv):
            assert float(jnp.max(jnp.abs(got[:, 128:]))) == 0.0
            assert float(jnp.max(jnp.abs(got[:, 5]))) == 0.0


@pytest.mark.parametrize("kind", KINDS[:3])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_the_three_kernels_give_the_blockwise_gradients(group, kind,
                                                        monkeypatch):
    """``splash_attention_and_lse`` differentiated (this module's forward,
    ``dq`` and ``dkv`` kernels through the ``custom_vjp``: the int8 copy of
    the mask and its kept blocks ride from the forward to the backward
    rule), in blocks of 128 so that there are four, against the blockwise
    form's gradients."""
    monkeypatch.setattr(sparse, "SPLASH_BLOCK", 128)
    q, k, v, ct = grouped_inputs(group)
    keep = selection(kind)
    got = with_gradients(lambda q, k, v: sparse.splash_attention_and_lse(
        q, k, v, keep, interpret=True)[0], q, k, v, ct)
    want = with_gradients(lambda q, k, v: sparse.blockwise_masked_attention(
        q, k, v, keep, q_chunk=64, kv_chunk=64), q, k, v, ct)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-3)
        assert float(jnp.max(jnp.abs(g))) > 0
    # and jax's own backward kernels' on the same forward, to float32
    out, lse = sparse.splash_attention_and_lse(q, k, v, keep, interpret=True)
    for g, w in zip(got[1:], jax_backward(q, k, v, out, lse, ct, keep)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)
