"""``ops/ssd.ssd`` against the token-by-token recurrence in float64 ``numpy``,
value and gradients, at lengths that are and are not a multiple of the chunk,
with the decay both near none and nearly total; a recurrence whose state is
reset at every chunk's edge must differ."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.ops import ssd as ssd_ops

H, G, P, N = 4, 2, 6, 5  # 4 heads of 6 in 2 groups, a [6, 5] state a head
CHUNK = 16


def recurrence(x, dt, a, b, c, d, reset_every=None):
    """The module docstring's two lines, a token at a time, float64: ``y [T,
    H, P]``."""
    x, dt, a, b, c, d = (np.asarray(u, np.float64)
                         for u in (x, dt, a, b, c, d))
    t_len, heads, _ = x.shape
    per = heads // b.shape[1]
    state = np.zeros((heads, x.shape[2], b.shape[2]))
    out = []
    for t in range(t_len):
        if reset_every and t % reset_every == 0:
            state = np.zeros_like(state)
        bt, ct = (np.repeat(u[t], per, axis=0) for u in (b, c))
        state = np.exp(dt[t] * a)[:, None, None] * state + np.einsum(
            "h,hp,hn->hpn", dt[t], x[t], bt)
        out.append(np.einsum("hpn,hn->hp", state, ct) + d[:, None] * x[t])
    return np.stack(out)


def recurrence_jnp(x, dt, a, b, c, d):
    """The same recurrence as a ``lax.scan`` over tokens in float64-free
    ``jax.numpy``: what autodiff differentiates for the gradient check."""
    per = x.shape[1] // b.shape[1]

    def token(state, xs):
        xt, dtt, bt, ct = xs
        bt, ct = (jnp.repeat(u, per, axis=0) for u in (bt, ct))
        state = jnp.exp(dtt * a)[:, None, None] * state \
            + dtt[:, None, None] * xt[:, :, None] * bt[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, ct,
                                 precision=ssd_ops.HI) + d[:, None] * xt

    state = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), jnp.float32)
    return jax.lax.scan(token, state, (x, dt, b, c))[1]


def inputs(seed, t_len, rate):
    """``rate`` scales the decay: ``dt a`` is near ``-rate`` a token."""
    r = np.random.default_rng(seed)
    x = r.normal(size=(t_len, H, P))
    dt = np.log1p(np.exp(r.normal(size=(t_len, H)) - 1.0))  # a softplus
    a = -rate * r.uniform(1.0, 4.0, size=(H,))
    b = r.normal(size=(t_len, G, N)) + 0.3
    c = r.normal(size=(t_len, G, N))
    d = r.normal(size=(H,))
    return tuple(jnp.asarray(u, jnp.float32) for u in (x, dt, a, b, c, d))


# 0.01 forgets little in a chunk; 40 leaves e^-500 of a state a token later
RATES = {"near_none": 0.01, "seeded": 1.0, "strong": 40.0}
LENGTHS = {"whole_chunks": 4 * CHUNK, "short_last": 3 * CHUNK + 5,
           "under_a_chunk": 7, "many_groups": 11 * CHUNK + 1}


@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("rate", sorted(RATES))
def test_chunked_form_is_the_recurrence(length, rate):
    args = inputs(1, LENGTHS[length], RATES[rate])
    got = ssd_ops.ssd(*args, chunk=CHUNK, group=2)
    want = recurrence(*args)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=2e-5,
                               atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("length", ["short_last", "whole_chunks"])
@pytest.mark.parametrize("rate", sorted(RATES))
def test_gradients_are_the_recurrences(length, rate):
    args = inputs(2, LENGTHS[length], RATES[rate])
    ct = jnp.asarray(np.random.default_rng(3).normal(
        size=args[0].shape), jnp.float32)
    loss = lambda f: lambda *xs: jnp.sum(f(*xs) * ct)  # noqa: E731
    every = tuple(range(6))
    got = jax.grad(loss(lambda *xs: ssd_ops.ssd(
        *xs, chunk=CHUNK, group=2)), every)(*args)
    want = jax.grad(loss(recurrence_jnp), every)(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        assert np.all(np.isfinite(g)), name
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-4 * float(jnp.abs(w).max()),
            err_msg=name)


def test_the_group_size_changes_nothing():
    args = inputs(4, LENGTHS["many_groups"], 1.0)
    one = ssd_ops.ssd(*args, chunk=CHUNK, group=1)
    for group in (3, 8, 64):
        np.testing.assert_allclose(
            ssd_ops.ssd(*args, chunk=CHUNK, group=group), one, rtol=1e-5,
            atol=1e-5)


def test_a_state_reset_at_every_chunks_edge_differs():
    """What the benchmark's second control computes: a scan that carries
    nothing across a chunk's edge is another function."""
    args = inputs(5, LENGTHS["whole_chunks"], 0.01)
    got = np.asarray(ssd_ops.ssd(*args, chunk=CHUNK, group=2))
    whole, reset = recurrence(*args), recurrence(*args, reset_every=CHUNK)
    # the first chunk has no edge behind it
    np.testing.assert_allclose(reset[:CHUNK], whole[:CHUNK])
    gap = np.linalg.norm(reset[CHUNK:] - whole[CHUNK:]) \
        / np.linalg.norm(whole[CHUNK:])
    assert gap > 0.3
    assert np.linalg.norm(got - whole) / np.linalg.norm(whole) < 1e-5
    assert np.linalg.norm(got - reset) / np.linalg.norm(whole) > 0.2


def test_products_in_bfloat16_stay_near_and_sum_in_float32():
    args = inputs(6, LENGTHS["short_last"], 1.0)
    got = ssd_ops.ssd(*args, dtype=jnp.bfloat16, chunk=CHUNK, group=2)
    want = recurrence(*args)
    assert got.dtype == jnp.float32
    gap = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert 1e-5 < gap < 2e-2


def test_heads_that_do_not_divide_into_groups_are_refused():
    x, dt, a, b, c, d = inputs(7, 8, 1.0)
    with pytest.raises(ValueError, match="groups"):
        ssd_ops.ssd(x[:, :3], dt[:, :3], a[:3], b, c, d[:3])


def test_the_published_chunk_is_the_default():
    assert ssd_ops.CHUNK == 128 and ssd_ops.GROUP == 4
