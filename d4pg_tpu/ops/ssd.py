"""The Mamba-2 state-space recurrence (SSD; Dao and Gu, arXiv:2405.21060): a
state a head that every token decays by a scalar, writes a rank-one update
into and reads.

``x [T, H, P]``, ``dt [T, H]`` (the step, already through its softplus: >= 0),
``a [H]`` (the decay rate, < 0), ``b``, ``c [T, G, N]`` (group ``g`` serves the
``H / G`` heads from ``g H / G`` on), ``d [H]`` (the skip), all float32. The
state ``S [P, N]`` of a head starts at zero and, token by token::

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T
    y_t = S_t c_t + d x_t

Run so it is ``T`` dependent steps; ``ssd`` is the chunked form of the same
sums (the paper's block decomposition), one form, plain ``jax.numpy``, on the
CPU and the chip alike. Inside a chunk of ``chunk`` tokens, with ``l`` the
running sum of ``dt a`` from the chunk's first token::

    y_i = sum_{j <= i} (c_i . b_j) exp(l_i - l_j) dt_j x_j      no step depends
                                                                on the state
          + exp(l_i) S c_i                   S the state the chunk starts from
    S <- exp(l_last) S + sum_j exp(l_last - l_j) dt_j x_j b_j^T

so the sequence is ``T / chunk`` dependent steps, and those are a decay and an
add. ``c_i . b_j`` is a group's, shared by its heads; the decay is a head's.
Every exponent is a difference ``l_i - l_j`` with ``j <= i`` or ``l`` itself,
never positive: a strongly negative ``dt a`` underflows to the zero it stands
for and nothing overflows. There is no write strength, no solve and no
normalisation (``ops/delta_rule.py`` has all three): the products are the
whole of it.

Precision. The decays, their sums and the state between chunks are float32.
The four products take their inputs in ``dtype`` (the torso's compute dtype,
bfloat16 on the chip; float32 products run at ``Precision.HIGHEST``) and sum
in float32, as the published kernels do: on the chip that is 7.4 ms for the
forward and backward of one 8,192-token sequence where float32 products take
9.5, 1.6e-3 of the output's norm apart (PERF.md section 6, PR 45).

Memory and the backward pass. Chunks are taken ``GROUP`` at a time: a group's
state-free products run batched, then its chunks' states one after another.
A group is rematerialised in the backward pass (``jax.checkpoint``), so the
gradient is autodiff's through the same sums, the states saved are one a
group (``T / (chunk * GROUP)`` of ``[H, P, N]`` float32: 16 x 2 MB for 8,192
tokens of 64 heads, fewer than one a chunk) and a group's intermediates (the
``[GROUP, H, chunk, chunk]`` decay-masked products, 17 MB) live for that group
alone. A length that is not a multiple of a group is zero-padded behind:
``dt = 0`` neither decays the state nor writes to it.

A reset inside a sequence (an episode's end; nothing asks for one yet) fits
these sums as a segment mask, not as a decay of zero: ``l`` through a
``-inf`` would make ``l_i - l_j`` undefined past it. With ``seg`` the count of
resets up to a token, the pairs kept are ``seg_i == seg_j``, the state a chunk
starts from is read where ``seg_i == seg_first`` and carried on where
``seg_last == seg_first``, and ``l`` restarts behind a reset.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
CHUNK = 128  # tokens a chunk: the published ``chunk_size``
# chunks a rematerialised group: forward and backward of an 8,192-token
# sequence of 64 heads take 7.58 ms at 2, 7.40 at 4, 7.75 at 8, 8.96 at 16 on
# the chip (bfloat16 products; float32 at HIGHEST 9.53 at 4, 9.52 at 8)
GROUP = 4


def _group(a, dtype, state, xs):
    """``GROUP`` chunks from ``state [G, R, P, N]`` (``R`` heads a group):
    ``xs`` are ``x [n, C, G, R, P]``, ``dt [n, C, G, R]``, ``b, c [n, C, G,
    N]``; returns the state after them and ``y [n, C, G, R, P]`` without the
    skip term."""
    x, dt, b, c = xs
    size = x.shape[1]
    dot = lambda spec, u, v: jnp.einsum(  # noqa: E731
        spec, u.astype(dtype), v.astype(dtype), precision=HI,
        preferred_element_type=jnp.float32)
    run = jnp.cumsum(dt * a, axis=1)  # [n, C, G, R], never positive
    lh = jnp.moveaxis(run, 1, -1)  # [n, G, R, C]
    rows, cols = jnp.arange(size)[:, None], jnp.arange(size)[None, :]
    # exp(l_i - l_j) where j <= i; the upper triangle never reaches exp
    decay = jnp.exp(jnp.where(cols <= rows,
                              lh[..., :, None] - lh[..., None, :], -jnp.inf))
    xdt = x * dt[..., None]
    pairs = dot("nigs,njgs->ngij", c, b)[:, :, None] * decay  # [n,G,R,C,C]
    y = dot("ngrij,njgrp->nigrp", pairs, xdt)
    last = run[:, -1]  # [n, G, R]
    written = dot("njgrp,njgs->ngrps",
                  xdt * jnp.exp(last[:, None] - run)[..., None], b)

    def chunk(state, xs):
        written, last = xs
        return state * jnp.exp(last)[..., None, None] + written, state

    state, entering = jax.lax.scan(chunk, state, (written, last))
    y = y + dot("nigs,ngrps->nigrp", c, entering) * jnp.exp(run)[..., None]
    return state, y


def ssd(x, dt, a, b, c, d, *, dtype=jnp.float32, chunk: int = CHUNK,
        group: int = GROUP):
    """``y [T, H, P]`` float32 of the recurrence in the module docstring."""
    t_len, heads, width = x.shape
    groups, n_state = b.shape[1:]
    if heads % groups:
        raise ValueError(f"{heads} heads do not divide into {groups} groups")
    per = heads // groups
    n = -(-t_len // chunk)
    group = min(group, n)
    n_groups = -(-n // group)
    pad = n_groups * group * chunk - t_len

    def blocks(u, tail):
        u = jnp.pad(u.astype(jnp.float32),
                    ((0, pad),) + ((0, 0),) * (u.ndim - 1))
        return u.reshape((n_groups, group, chunk) + tail)

    state = jnp.zeros((groups, per, width, n_state), jnp.float32)
    body = lambda state, xs: _group(  # noqa: E731
        a.astype(jnp.float32).reshape(groups, per), dtype, state, xs)
    _, y = jax.lax.scan(jax.checkpoint(body), state, (
        blocks(x, (groups, per, width)), blocks(dt, (groups, per)),
        blocks(b, (groups, n_state)), blocks(c, (groups, n_state))))
    return y.reshape(-1, heads, width)[:t_len] + d[:, None] * x
