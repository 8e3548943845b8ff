"""``ops/delta_rule.gated_delta_rule`` against the token-by-token recurrence
in float64 ``numpy``, value and gradients, at lengths that are not one chunk
and with the decay both near none and nearly total: at small heads, a key
head a value head, and at the chip's lane-wide heads with one key head
serving two value heads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from d4pg_tpu.ops import delta_rule

T, H, DK, DV = 200, 3, 8, 6  # 200 tokens: four chunks of 64, the last short
# the sizes a test runs at: value heads, key heads, Dk, Dv
SIZES = {"small": (H, H, DK, DV), "lane_wide": (2, 1, 128, 128)}
both_sizes = pytest.mark.parametrize("sizes", sorted(SIZES))


def by_value_head(sizes, q, k, *rest):
    """The inputs as the recurrence reads them: a key head repeated to the
    value heads it serves."""
    heads, key_heads = SIZES[sizes][:2]
    return (np.repeat(q, heads // key_heads, axis=1),
            np.repeat(k, heads // key_heads, axis=1)) + rest


def by_key_head(sizes, grads):
    """The recurrence's gradients as the inputs take them: those of
    ``q`` and ``k`` summed over the value heads a key head serves."""
    heads, key_heads = SIZES[sizes][:2]
    fold = lambda x: x.reshape(  # noqa: E731
        x.shape[0], key_heads, heads // key_heads, -1).sum(axis=2)
    return [fold(grads[0]), fold(grads[1])] + list(grads[2:])


def recurrence(q, k, v, g, beta, reset_every=None):
    """The module docstring's four lines, a token at a time, float64:
    ``(o [T, H, Dv], states after every token [T, H, Dk, Dv])``."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    t_len, heads, dk = q.shape
    state = np.zeros((heads, dk, v.shape[-1]))
    out, states = [], []
    for t in range(t_len):
        if reset_every and t % reset_every == 0:
            state = np.zeros_like(state)
        state = np.exp(g[t])[:, None, None] * state
        d = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", state, k[t]))
        state = state + np.einsum("hk,hv->hkv", k[t], d)
        out.append(np.einsum("hkv,hk->hv", state, q[t]))
        states.append(state)
    return np.stack(out), np.stack(states)


def recurrence_grads(q, k, v, g, beta, ct):
    """Gradients of ``sum(o * ct)`` by the recurrence run backwards by hand,
    float64: with ``G`` the state's cotangent after token ``t``,
    ``G += q_t ct_t^T``; ``dk_t = G d_t - beta_t exp(g_t) S_{t-1} (G^T k_t)``
    and so on, line by line."""
    q, k, v, g, beta, ct = (np.asarray(x, np.float64)
                            for x in (q, k, v, g, beta, ct))
    t_len = q.shape[0]
    _, states = recurrence(q, k, v, g, beta)
    prev = np.concatenate([np.zeros_like(states[:1]), states[:-1]])
    grads = [np.zeros_like(x) for x in (q, k, v, g, beta)]
    dq, dk, dv, dg, db = grads
    carry = np.zeros_like(states[0])  # dL/dS_t from later tokens
    for t in reversed(range(t_len)):
        decayed = np.exp(g[t])[:, None, None] * prev[t]  # S'
        resid = v[t] - np.einsum("hkv,hk->hv", decayed, k[t])
        d = beta[t][:, None] * resid
        dq[t] = np.einsum("hkv,hv->hk", states[t], ct[t])
        total = carry + np.einsum("hk,hv->hkv", q[t], ct[t])  # dL/dS_t
        dd = np.einsum("hkv,hk->hv", total, k[t])
        dk[t] = np.einsum("hkv,hv->hk", total, d)
        db[t] = np.sum(dd * resid, axis=-1)
        dresid = beta[t][:, None] * dd
        dv[t] = dresid
        dk[t] -= np.einsum("hkv,hv->hk", decayed, dresid)
        ddecayed = total - np.einsum("hk,hv->hkv", k[t], dresid)
        dg[t] = np.sum(ddecayed * decayed, axis=(1, 2))
        carry = np.exp(g[t])[:, None, None] * ddecayed
    return grads


def inputs(seed, log_decay, t_len=T, sizes="small"):
    heads, key_heads, dk, dv = SIZES[sizes]
    r = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(r.normal(size=(t_len, key_heads, dk))) * dk ** -0.5
    # keys that are not zero-mean, as behind a SiLU: their products are not
    # small and the solve has work to do
    k = unit(r.normal(size=(t_len, key_heads, dk)) + 0.7)
    v = r.normal(size=(t_len, heads, dv))
    g = -np.exp(r.normal(size=(t_len, heads)) + log_decay)
    beta = 1.0 / (1.0 + np.exp(-r.normal(size=(t_len, heads))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


# log of the typical -g: exp(-5) forgets nothing in a chunk, exp(1.5) ~ 4.5 a
# token leaves e^-288 of a state at a chunk's end (an underflow to zero)
DECAYS = {"near_none": -5.0, "seeded": -1.5, "strong": 1.5}


@both_sizes
@pytest.mark.parametrize("log_decay", sorted(DECAYS.values()),
                         ids=sorted(DECAYS, key=DECAYS.get))
def test_the_chunked_form_is_the_recurrence(log_decay, sizes):
    xs = inputs(1, log_decay, sizes=sizes)
    want, _ = recurrence(*by_value_head(sizes, *xs))
    got = np.asarray(delta_rule.gated_delta_rule(*xs))
    assert got.shape == (T,) + xs[2].shape[1:] and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert np.abs(want).max() > 0.05  # not a comparison of zeros


@both_sizes
@pytest.mark.parametrize("log_decay", sorted(DECAYS.values()),
                         ids=sorted(DECAYS, key=DECAYS.get))
def test_its_gradients_are_the_recurrences_for_all_five_inputs(log_decay,
                                                               sizes):
    xs = inputs(2, log_decay, sizes=sizes)
    ct = jnp.asarray(np.random.default_rng(3).normal(size=xs[2].shape),
                     jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(delta_rule.gated_delta_rule(*a) * ct),
                   argnums=(0, 1, 2, 3, 4))(*xs)
    want = by_key_head(sizes, recurrence_grads(*by_value_head(sizes, *xs), ct))
    for name, a, b in zip("q k v g beta".split(), got, want):
        scale = np.abs(b).max()
        assert scale > 1e-3, name
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-3,
                                   atol=2e-4 * scale, err_msg=name)


def test_the_hand_written_backward_is_autodiffs_of_the_recurrence():
    """The float64 gradients above against ``jax.grad`` of a token-by-token
    ``lax.scan``: the yardstick is checked before it is used."""
    xs = inputs(4, -1.5, t_len=40)
    ct = jnp.asarray(np.random.default_rng(5).normal(size=(40, H, DV)),
                     jnp.float32)

    def token_by_token(q, k, v, g, beta):
        def step(state, x):
            q, k, v, g, beta = x
            state = jnp.exp(g)[:, None, None] * state
            d = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
            state = state + jnp.einsum("hk,hv->hkv", k, d)
            return state, jnp.einsum("hkv,hk->hv", state, q)
        return jax.lax.scan(step, jnp.zeros((H, DK, DV)),
                            (q, k, v, g, beta))[1]

    auto = jax.grad(lambda *a: jnp.sum(token_by_token(*a) * ct),
                    argnums=(0, 1, 2, 3, 4))(*xs)
    for a, b in zip(auto, recurrence_grads(*xs, ct)):
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-3, atol=1e-5)


@both_sizes
@pytest.mark.parametrize("t_len", [1, 63, 64, 65, 16 * 64 + 1])
def test_any_length_is_served(t_len, sizes):
    xs = inputs(6, -1.5, t_len, sizes)
    np.testing.assert_allclose(
        np.asarray(delta_rule.gated_delta_rule(*xs)),
        recurrence(*by_value_head(sizes, *xs))[0], rtol=3e-4, atol=3e-5)


@both_sizes
def test_nothing_before_a_token_reads_it_and_chunks_later_it_is_read(sizes):
    # a slow decay: token 0 is still in the state
    xs = inputs(7, -4.0, sizes=sizes)
    base = np.asarray(delta_rule.gated_delta_rule(*xs))
    at = 70  # inside the second chunk
    for i, x in enumerate(xs):
        moved = list(xs)
        moved[i] = x.at[at].set(x[at] * 0.5 + 0.1 * (-1 if i == 3 else 1))
        out = np.asarray(delta_rule.gated_delta_rule(*moved))
        np.testing.assert_array_equal(out[:at], base[:at])  # causal
        if i:  # q_t is read by o_t alone
            assert np.abs(out[at + 1:] - base[at + 1:]).max() > 1e-6
        else:
            np.testing.assert_array_equal(out[at + 1:], base[at + 1:])
            assert np.abs(out[at] - base[at]).max() > 1e-6
    # memory: the first token's value moves the output three chunks later
    # (weak writes: 192 tokens do not overwrite a key space)
    xs = xs[:4] + (0.05 * xs[4],)
    base = np.asarray(delta_rule.gated_delta_rule(*xs))
    moved = list(xs)
    moved[2] = xs[2].at[0].add(20.0)
    out = np.asarray(delta_rule.gated_delta_rule(*moved))
    scale = np.abs(base).max()  # 0.05 at 8-wide heads, 0.006 at 128-wide
    assert np.abs(out[3 * 64:] - base[3 * 64:]).max() > 1e-3 * scale
    # and a state reset at every chunk's first token is another function
    reset, _ = recurrence(*by_value_head(sizes, *xs), reset_every=64)
    assert np.abs(reset[:64] - base[:64]).max() < 2e-3 * scale
    assert np.abs(reset[64:] - base[64:]).max() > 0.2 * scale


@both_sizes
def test_a_padded_tail_leaves_the_state_alone(sizes):
    """Two groups where one would do (a group of one chunk): the same
    numbers, so the zero padding behind the last token changes nothing and
    the state crosses a group's edge as it crosses a chunk's."""
    xs = inputs(8, -1.5, t_len=130, sizes=sizes)
    a = delta_rule.gated_delta_rule(*xs)
    b = delta_rule.gated_delta_rule(*xs, group=1)
    c = delta_rule.gated_delta_rule(*xs, chunk=16, group=3)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-5)


@pytest.mark.parametrize("size", [4, 8, 16, 64])
def test_the_solve_by_blocks_is_the_inverse_with_keys_that_all_agree(size):
    """``(I + A)^-1`` at the hardest ``A`` the rule can hand it: every key
    the same, ``beta`` 1 and no decay, so all of ``A`` under the diagonal is
    1 and its powers grow as binomials (the series over a whole chunk of 64
    would take sums near 1e17 through float32; over a block of 8 they stay
    under 35). The true inverse of the first has 1 on the diagonal and -1
    right under it; the second scales the entries at random."""
    a = np.tril(np.ones((2, 3, size, size), np.float32), -1)
    a[1] *= np.random.default_rng(9).uniform(0.0, 1.0, a[1].shape)
    got = np.asarray(delta_rule._inverse(jnp.asarray(a)))
    want = np.linalg.inv(np.eye(size) + a.astype(np.float64))
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.array_equal(np.triu(got, 1), np.zeros_like(got))


def test_a_chunk_the_solve_cannot_halve_is_refused():
    xs = inputs(10, -1.5, t_len=48)
    with pytest.raises(ValueError, match="power of two"):
        delta_rule.gated_delta_rule(*xs, chunk=24)
    assert delta_rule.gated_delta_rule(*xs, chunk=4).shape == (48, H, DV)


def test_the_solves_gradient_is_the_inverses():
    """``d sum(T * ct) / d a = -T^T ct T^T`` for ``T = (I + a)^-1``, under
    the diagonal: autodiff through the stacked, lane-batched blocks."""
    r = np.random.default_rng(11)
    a = np.tril(r.uniform(0.0, 1.0, (2, 3, 64, 64)), -1).astype(np.float32)
    ct = r.normal(size=a.shape).astype(np.float32)
    got = jax.grad(lambda a: jnp.sum(delta_rule._inverse(a) * ct))(
        jnp.asarray(a))
    inv = np.linalg.inv(np.eye(64) + a.astype(np.float64))
    want = np.tril(
        -np.swapaxes(inv, -1, -2) @ ct @ np.swapaxes(inv, -1, -2), -1)
    np.testing.assert_allclose(np.tril(np.asarray(got), -1), want,
                               atol=1e-5 * np.abs(want).max())


def test_a_key_heads_products_serve_its_value_heads():
    """One key head for two value heads is two key heads that agree: the
    products ``k_i . k_j`` and ``q_i . k_j`` are made a key head, then
    handed to the heads it serves."""
    q, k, v, g, beta = inputs(12, -1.5, sizes="lane_wide")
    shared = delta_rule.gated_delta_rule(q, k, v, g, beta)
    apart = delta_rule.gated_delta_rule(
        jnp.repeat(q, 2, axis=1), jnp.repeat(k, 2, axis=1), v, g, beta)
    np.testing.assert_allclose(np.asarray(shared), np.asarray(apart),
                               atol=1e-7)
