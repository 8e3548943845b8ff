"""The gated delta rule (Gated DeltaNet; Yang, Kautz, Hatamizadeh,
arXiv:2412.06464): a linear-attention state a head that every token decays,
corrects and reads.

``q``, ``k [T, Hk, Dk]``, ``v [T, H, Dv]``, ``g``, ``beta [T, H]``, all
float32 (``g <= 0`` the log of the decay, ``beta`` the write strength; ``q``
and ``k`` come normalised and scaled; key head ``i`` serves the ``H / Hk``
value heads from ``i H / Hk`` on). The state ``S [Dk, Dv]`` of a value head
starts at zero and, token by token::

    S' = exp(g_t) S
    d_t = beta_t (v_t - S'^T k_t)
    S_t = S' + k_t d_t^T
    o_t = S_t^T q_t

Run so it is ``T`` dependent steps; ``gated_delta_rule`` is the chunked form
of the same sums (the WY representation of the published kernels), one form,
plain ``jax.numpy``, on the CPU and the chip alike. Inside a chunk of
``CHUNK`` tokens, with ``c`` the running sum of ``g`` from the chunk's first
token and ``A[i, j] = beta_i (k_i . k_j) exp(c_i - c_j)`` for ``j < i``::

    (I + A) [u | w] = [beta v | beta k exp(c)]     a unit lower-triangular
                                                   system: no step depends
                                                   on the state
    d = u - w S                                    S the state the chunk
                                                   starts from
    o = (q exp(c)) S + tril((q k^T) exp(c_i - c_j)) d
    S <- exp(c_last) S + (k exp(c_last - c))^T d

so the sequence is ``T / CHUNK`` dependent steps of matrix products. Every
exponent is a difference ``c_i - c_j`` with ``j <= i`` or ``c`` itself, never
positive: a strongly negative ``g`` underflows to the zero it stands for and
nothing overflows. Products are float32 at ``Precision.HIGHEST`` (the solve
amplifies what enters it).

The solve is products too (``_inverse``): ``A`` is nilpotent, so a diagonal
block of ``BLOCK`` tokens has the finite inverse ``(I - A)(I + A^2)(I + A^4)``
(two squarings; powers of an 8-token block stay small, where the same series
over a whole chunk would take sums of binomial size through float32), and
two inverted neighbours ``T``, ``B`` under the block ``F`` between them merge
exactly into ``[[T, 0], [-B F T, B]]``, by halves up to the chunk: the
published kernels' scheme. On the chip the scan takes 0.6 of its time with
XLA's ``triangular-solve`` custom call, for the same numbers to 2e-7
(PERF.md section 6, PR 38).

Memory and the backward pass. Chunks are taken ``GROUP`` at a time: the
solve and the other state-free products of a group run batched, then its
chunks one after another. A group is rematerialised in the backward pass
(``jax.checkpoint``), so the gradient is autodiff's through the same sums,
the states saved are one a group (``T / (CHUNK * GROUP)`` of them, not one a
chunk: 64 x 2 MB for 16,384 tokens of 32 heads, not 537 MB) and a group's
intermediates live for that group alone; the price is the group's forward
made once more. Key heads are repeated to their value heads inside a group,
so what the backward pass keeps of ``q`` and ``k`` is ``Hk`` heads wide. A
length that is not a multiple of a group is zero-padded behind: ``k = 0``,
``beta = 0``, ``g = 0`` leave the state as it is.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
CHUNK = 64  # tokens a chunk: the published kernels'
# chunks a rematerialised group: forward and backward of a 16,384-token
# sequence take 72.7 ms at 4, 75.2 at 8, 87.6 at 16, 104.5 at 32 on the chip
GROUP = 4
BLOCK = 8  # tokens a diagonal block of the solve is inverted by its series


def _inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a [..., C, C]``, ``C``
    ``BLOCK`` times a power of two (or less than ``BLOCK``): the module
    docstring's series on the diagonal blocks, merged by halves. A block is
    an array of its own throughout (on the chip a stacked ``[..., C / 8, 8,
    8]`` takes a third longer: PERF.md section 6, PR 38)."""
    size = a.shape[-1]
    mm = lambda x, y: jnp.einsum(  # noqa: E731
        "...ab,...bc->...ac", x, y, precision=HI)
    b = min(BLOCK, size)
    invs = []
    for i in range(0, size, b):
        m = a[..., i:i + b, i:i + b]
        inv, power = jnp.eye(b, dtype=a.dtype) - m, m
        for _ in range((b - 1).bit_length() - 1):
            power = mm(power, power)
            inv = inv + mm(inv, power)
        invs.append(inv)
    while len(invs) > 1:
        merged = []
        for i, (top, bottom) in enumerate(zip(invs[0::2], invs[1::2])):
            lo = 2 * b * i
            under = -mm(mm(bottom, a[..., lo + b:lo + 2 * b, lo:lo + b]), top)
            merged.append(jnp.concatenate([
                jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
                jnp.concatenate([under, bottom], axis=-1)], axis=-2))
        invs, b = merged, 2 * b
    return invs[0]


def _group(state, xs):
    """``GROUP`` chunks from ``state [H, Dk, Dv]``: ``xs`` are ``q, k [n, C,
    Hk, Dk]``, ``v [n, C, H, Dv]``, ``g, beta [n, C, H]``; returns the state
    after them and ``o [n, C, H, Dv]``."""
    q, k, v, g, beta = xs
    q, k = (jnp.repeat(x, v.shape[2] // x.shape[2], axis=2) for x in (q, k))
    size = q.shape[1]
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HI)  # noqa
    c = jnp.cumsum(g, axis=1)  # [n, C, H]
    ch = c.transpose(0, 2, 1)  # [n, H, C]
    rows, cols = jnp.arange(size)[:, None], jnp.arange(size)[None, :]
    # exp(c_i - c_j) where j <= i; the upper triangle never reaches exp
    decay = jnp.exp(jnp.where(cols <= rows,
                              ch[..., :, None] - ch[..., None, :], -jnp.inf))
    kb, vb = k * beta[..., None], v * beta[..., None]
    a = jnp.where(cols < rows, dot("nchd,nehd->nhce", kb, k) * decay, 0.0)
    rhs = jnp.concatenate([vb, kb * jnp.exp(c)[..., None]], axis=-1)
    sol = dot("nhce,nehd->nhcd", _inverse(a), rhs)  # [n, H, C, Dv + Dk]
    u, w = sol[..., :v.shape[-1]], sol[..., v.shape[-1]:]
    qk = dot("nchd,nehd->nhce", q, k) * decay
    last = c[:, -1]  # [n, H]
    q_in = q * jnp.exp(c)[..., None]
    k_out = k * jnp.exp(last[:, None] - c)[..., None]

    def chunk(state, xs):
        u, w, qk, q_in, k_out, last = xs
        d = u - dot("hck,hkv->hcv", w, state)
        o = dot("chk,hkv->chv", q_in, state) + dot("hce,hev->chv", qk, d)
        state = state * jnp.exp(last)[:, None, None] \
            + dot("chk,hcv->hkv", k_out, d)
        return state, o

    return jax.lax.scan(chunk, state, (u, w, qk, q_in, k_out, last))


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = CHUNK,
                     group: int = GROUP):
    """``o [T, H, Dv]`` float32 of the recurrence in the module docstring."""
    t_len, _key_heads, dk = q.shape
    heads, dv = v.shape[1:]
    halves = chunk // BLOCK  # _inverse merges its blocks two and two
    if chunk > BLOCK and (chunk % BLOCK or halves & (halves - 1)):
        raise ValueError(f"a chunk of {chunk} tokens is not {BLOCK} times a "
                         f"power of two")
    n = -(-t_len // chunk)
    group = min(group, n)
    n_groups = -(-n // group)
    pad = n_groups * group * chunk - t_len

    def blocks(x):
        x = jnp.pad(x.astype(jnp.float32),
                    ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n_groups, group, chunk) + x.shape[1:])

    state = jnp.zeros((heads, dk, dv), jnp.float32)
    _, o = jax.lax.scan(jax.checkpoint(_group), state,
                        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return o.reshape(-1, heads, dv)[:t_len]
