"""Seeded inputs: replay rows, priorities and weights as pure functions of
(seed, index), so the fill, the actor threads and the plain reference all
regenerate the same values without handing arrays to one another.

A counter hash (murmur3's 32-bit finaliser, twice) stands in for a PRNG: it
is elementwise, so 13 GB of pixel rows are made on the device at memory
speed, and row ``i`` can be made alone. Every function takes the array
namespace ``xp`` (``numpy`` on actor threads, ``jax.numpy`` on the device);
integer results agree between the two bit for bit, float results are only
ever compared with values from the same namespace.
"""

from __future__ import annotations

import numpy as np

# field ids: one hash stream per (purpose, field)
OBS, ACTION, REWARD, NEXT_OBS, PRIORITY, WEIGHT = 1, 2, 3, 4, 5, 6

_U32 = 0xFFFFFFFF


def fold_seed(seed: int) -> int:
    """Any whole number (the driver's exceed 31 bits) to 32 bits."""
    seed = int(seed)
    return (seed ^ (seed >> 32) ^ (seed >> 64)) & _U32


def _mix(xp, x):
    x = x ^ (x >> 16)
    x = x * xp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * xp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def bits(xp, seed32, field: int, rows, n_cols: int, salt: int = 0):
    """uint32 [len(rows), n_cols]: hash of (seed, field, row, column)."""
    rows = xp.asarray(rows).astype(xp.uint32)
    base = xp.asarray(seed32).astype(xp.uint32) ^ xp.uint32(
        (field * 0x9E3779B1 + salt * 0x7F4A7C15) & _U32)
    h = _mix(xp, rows * xp.uint32(0x9E3779B1) + base)
    cols = xp.arange(n_cols, dtype=xp.uint32)
    return _mix(xp, h[:, None] ^ (cols[None, :] * xp.uint32(0x85EBCA77)
                                  + xp.uint32(0x165667B1)))


def uniform(xp, seed32, field, rows, n_cols, salt=0):
    """float32 in [0, 1), 24 bits."""
    b = bits(xp, seed32, field, rows, n_cols, salt)
    return (b >> 8).astype(xp.float32) * xp.float32(2.0 ** -24)


def normal(xp, seed32, field, rows, n_cols):
    """Standard normal float32 by Box-Muller from two hash streams."""
    u1 = uniform(xp, seed32, field, rows, n_cols, salt=1)
    u2 = uniform(xp, seed32, field, rows, n_cols, salt=2)
    r = xp.sqrt(-2.0 * xp.log(u1 + xp.float32(2.0 ** -25)))
    return (r * xp.cos(xp.float32(2.0 * np.pi) * u2)).astype(xp.float32)


def rows(xp, seed32, idx, spec: dict) -> tuple:
    """Transition rows ``idx`` as ``(obs, action, reward, next_obs, done,
    discount)``. ``spec``: ``obs_shape`` (a 1-tuple is a state vector of
    standard normals, a 3-tuple is uint8 pixels), ``act_dim``,
    ``discount``. ``done`` is 0 here; the ingest driver stamps a sequence
    number there (the update never reads ``done``)."""
    idx = xp.asarray(idx)
    n = idx.shape[0]
    shape = tuple(spec["obs_shape"])
    width = int(np.prod(shape))
    if len(shape) == 1:
        obs = normal(xp, seed32, OBS, idx, width)
        nxt = normal(xp, seed32, NEXT_OBS, idx, width)
    else:
        obs = (bits(xp, seed32, OBS, idx, width) >> 24).astype(xp.uint8)
        nxt = (bits(xp, seed32, NEXT_OBS, idx, width) >> 24).astype(xp.uint8)
    obs = obs.reshape((n,) + shape)
    nxt = nxt.reshape((n,) + shape)
    action = 2.0 * uniform(xp, seed32, ACTION, idx, int(spec["act_dim"])) - 1.0
    reward = normal(xp, seed32, REWARD, idx, 1)[:, 0]
    done = xp.zeros((n,), xp.float32)
    discount = xp.full((n,), spec["discount"], xp.float32)
    return (obs, action.astype(xp.float32), reward, nxt, done, discount)


def priorities(xp, seed32, idx, decades: float):
    """Raw priorities log-uniform on [10**-decades, 1], so the stratified
    descent is not degenerate."""
    u = uniform(xp, seed32, PRIORITY, idx, 1)[:, 0]
    return xp.exp(-xp.float32(decades * np.log(10.0)) * u).astype(xp.float32)


def weights(xp, seed32, template):
    """Network weights for a flax-shaped ``template`` tree of
    shape/dtype leaves: every ``kernel`` is N(0, 1/fan_in) (heads too, so
    the seeded critic is not a uniform distribution whatever the
    precision), every ``bias`` 0, every ``scale`` 1. Leaf ``i`` in tree
    order draws from hash row ``i`` upward, so a tree's values depend only
    on (seed, its own shapes)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        shape = tuple(leaf.shape)
        if name == "kernel":
            fan_in = int(np.prod(shape[:-1]))
            rows_ = xp.arange(fan_in, dtype=xp.uint32) + xp.uint32(
                (i * 0x01000193) & _U32)
            w = normal(xp, seed32, WEIGHT, rows_, shape[-1])
            out.append((w / np.sqrt(fan_in)).reshape(shape))
        elif name == "scale":
            out.append(xp.ones(shape, xp.float32))
        else:
            out.append(xp.zeros(shape, xp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)
