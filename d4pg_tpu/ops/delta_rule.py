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
published kernels' scheme. These blocks are 8 to 32 wide, a fraction of a
lane tile, so they are laid ``[blocks, rows, columns, batch]`` with a
group's chunks and heads as the batch, last: a product of blocks is a
broadcast multiply summed over the middle index, elementwise along the
batch (exact float32, no pass of the MXU), all blocks of a level in one
launch. Laid ``[batch, 8, 8]`` every block was a lane tile of its own,
sixteen times its size, and every product of two blocks a launch: 5 of the
14.5 ms a forward pass of 16,384 tokens took on the chip (10.2 so; PERF.md
section 6, PR 47). XLA's ``triangular-solve`` custom call gives the same
numbers to 2e-7 and took the scan 1.7 times as long (PR 38).

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
# sequence take 40.2 ms at 4 and 47.9 at 8 on the chip (PR 47: at 4 a group
# of 32 heads is the 128 lanes of the solve's batch and its backward stays
# in VMEM; 72.7 / 75.2 / 87.6 / 104.5 at 4 / 8 / 16 / 32 at PR 38)
GROUP = 4
BLOCK = 8  # tokens a diagonal block of the solve is inverted by its series


def _inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a [..., C, C]``, ``C``
    ``BLOCK`` times a power of two (or less than ``BLOCK``): the module
    docstring's series on the diagonal blocks, merged by halves, the blocks
    of a level stacked ``[blocks, b, b, batch]`` with every leading
    dimension of ``a`` as the batch."""
    size = a.shape[-1]
    at = jnp.moveaxis(a.reshape((-1, size, size)), 0, -1)  # [C, C, batch]
    mm = lambda x, y: jnp.sum(  # noqa: E731
        x[:, :, :, None] * y[:, None, :, :], axis=2)
    b = min(BLOCK, size)
    m = jnp.stack([at[i:i + b, i:i + b] for i in range(0, size, b)])
    inv, power = jnp.eye(b, dtype=a.dtype)[:, :, None] - m, m
    for _ in range((b - 1).bit_length() - 1):
        power = mm(power, power)
        inv = inv + mm(inv, power)
    while inv.shape[0] > 1:
        top, bottom = inv[0::2], inv[1::2]
        between = jnp.stack([at[lo + b:lo + 2 * b, lo:lo + b]
                             for lo in range(0, size, 2 * b)])
        under = -mm(mm(bottom, between), top)
        inv = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], axis=2),
            jnp.concatenate([under, bottom], axis=2)], axis=1)
        b *= 2
    return jnp.moveaxis(inv[0], -1, 0).reshape(a.shape)


def _group(state, xs):
    """``GROUP`` chunks from ``state [H, Dk, Dv]``: ``xs`` are ``q, k [n, C,
    Hk, Dk]``, ``v [n, C, H, Dv]``, ``g, beta [n, C, H]``; returns the state
    after them and ``o [n, C, H, Dv]``."""
    q, k, v, g, beta = xs
    served = v.shape[2] // q.shape[2]  # value heads a key head
    size = q.shape[1]
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HI)  # noqa
    c = jnp.cumsum(g, axis=1)  # [n, C, H]
    ch = c.transpose(0, 2, 1)  # [n, H, C]
    rows, cols = jnp.arange(size)[:, None], jnp.arange(size)[None, :]
    # exp(c_i - c_j) where j <= i; the upper triangle never reaches exp
    decay = jnp.exp(jnp.where(cols <= rows,
                              ch[..., :, None] - ch[..., None, :], -jnp.inf))
    # k_i . k_j and q_i . k_j are a key head's: made once for the value
    # heads it serves
    kk = jnp.repeat(dot("nchd,nehd->nhce", k, k), served, axis=1)
    qk = jnp.repeat(dot("nchd,nehd->nhce", q, k), served, axis=1) * decay
    a = jnp.where(cols < rows,
                  kk * beta.transpose(0, 2, 1)[..., None] * decay, 0.0)
    q, k = (jnp.repeat(x, served, axis=2) for x in (q, k))
    kb, vb = k * beta[..., None], v * beta[..., None]
    rhs = jnp.concatenate([vb, kb * jnp.exp(c)[..., None]], axis=-1)
    sol = dot("nhce,nehd->nhcd", _inverse(a), rhs)  # [n, H, C, Dv + Dk]
    u, w = sol[..., :v.shape[-1]], sol[..., v.shape[-1]:]
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
