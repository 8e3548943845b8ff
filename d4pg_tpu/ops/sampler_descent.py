"""Pallas TPU kernel for the PER stratified prefix-sum descent.

The dealt plane's sample step (``replay/device_sampler.py``) is a batch
of inverse-CDF descents through the device sum tree — a memory-bound
gather loop with log2(capacity) dependent rounds. The baseline arm keeps
it as plain ``jnp`` gathers (``device_per.descend``), which XLA lowers to
one dynamic-gather per level; this kernel is the Pallas arm of the
``--sampler`` autotune surface (``ops/autotune.select_sampler``): the
whole sum tree is pinned in VMEM for the duration of a query tile, so
the log2(N) rounds never re-touch HBM.

TPU VMEM has no vectorized dynamic gather, so each level's
``left_sum = tree[2 * node]`` is computed as a chunked ONE-HOT
contraction over the tree: for every tree chunk, ``where(j == left,
tree_j, 0)`` summed over the chunk. Exactly one summand is nonzero and
float32 ``x + 0.0 == x`` is exact, so the result is BITWISE the gathered
value — the kernel and the ``jnp`` descent arm agree bit-for-bit, which
is what lets the seeded-stream oracle pin either arm against the host
dealer (tests/test_devsample.py).

The tree enters the kernel as a 2-D ``[2 * capacity / 512, 512]`` block
and the queries as ``[128, 1]`` columns (Mosaic tiles are 2-D); both the
level loop and the per-row contraction loop are ``lax.fori_loop``s, so
the kernel body is constant-size whatever the capacity (unrolled in
Python it would be ``levels * 2 * capacity / 512`` copies — tens of
thousands at ring size, which does not compile in bounded time). At level
``l`` only the first ``2 ** (l + 2)`` nodes can be hit, so the
contraction reads only those rows.

Fit bound: the tree block is ``2 * capacity`` float32 in VMEM, double-
buffered by the pipeline, so the call raises the scoped-VMEM limit to
twice the block plus headroom; past ``_VMEM_TREE_BYTES`` (capacity >
1.5M slots) the kernel refuses and the autotuner falls back to the
``jnp`` arm. Compiles with ``interpret=False`` on a v5e at 2**18 and
2**20 slots and is bitwise-equal to ``device_per.descend`` there
(``chip_smoke.py`` re-checks this on every run). Runs under
``interpret=True`` on CPU for tests; on CPU the autotuner never selects
it (interpret mode measures the emulator, not a kernel — same policy as
``ops/projection.py``, which is also honest about losing its race: the
one-hot contraction does O(capacity) work per query tile against the
gather's O(log capacity), so this arm only wins where VMEM residency
beats HBM gather latency, an empirical fact ``--sampler auto`` measures
on chip).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE_Q = 128  # queries per grid step
_CHUNK = 512  # tree nodes per one-hot contraction round (one tree row)

# VMEM budget for the resident tree block (bytes); past this the caller
# must use the jnp gather arm (pallas_fits / select_sampler gate it).
_VMEM_TREE_BYTES = 12 * 1024 * 1024


def pallas_fits(capacity: int) -> bool:
    """Whether the [2 * capacity] float32 tree block fits the VMEM budget."""
    return 2 * int(capacity) * 4 <= _VMEM_TREE_BYTES


def _descent_kernel(tree_ref, mass_ref, idx_ref, *, levels, cap, chunk):
    # tree_ref: [2 * cap / chunk, chunk] — the flat tree as rows of
    # ``chunk`` lanes, VMEM-resident across all levels; mass_ref/idx_ref:
    # [TQ, 1] query columns (2-D blocks: Mosaic tiles are (8, 128))
    p0 = mass_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    n_rows = tree_ref.shape[0]

    def level(lvl, carry):
        p, node = carry
        left = node * 2

        # one-hot gather of tree[left], one tree row per round so the
        # [TQ, chunk] compare/select temporary stays small; only the hit
        # row contributes a nonzero summand (bitwise-exact, see module
        # doc). At level ``lvl`` every ``left`` is < 2 ** (lvl + 2), so
        # only the rows below that bound can hit.
        def row_round(r, acc):
            row = tree_ref[pl.ds(r, 1), :]  # [1, chunk]
            hit = (lane + r * chunk) == left  # [TQ, chunk]
            return acc + jnp.sum(jnp.where(hit, row, 0.0), axis=1,
                                 keepdims=True)

        live_rows = jnp.minimum(
            n_rows, (jnp.left_shift(4, lvl) + chunk - 1) // chunk)
        left_sum = jax.lax.fori_loop(
            0, live_rows, row_round, jnp.zeros(p.shape, jnp.float32))
        # the shared tie rule (device_per.descend): mass >= left sum
        # descends RIGHT — left is even, so ``left + 1`` is ``left | 1``
        go_right = p >= left_sum
        p = jnp.where(go_right, p - left_sum, p)
        node = jnp.where(go_right, left + 1, left)
        return p, node

    _, node = jax.lax.fori_loop(
        0, levels, level, (p0, jnp.ones(p0.shape, jnp.int32)))
    idx_ref[...] = node - cap


@functools.partial(jax.jit, static_argnums=(2,))
def descend_pallas(sum_tree: Array, mass: Array,
                   interpret: bool = False) -> Array:
    """Drop-in Pallas variant of ``device_per.descend`` (flat queries).

    sum_tree: [2 * capacity] float32; mass: [Q] float32 prefix masses.
    Q pads up to the query tile internally; [Q] int32 slots come back
    exact and bitwise-equal to the jnp descent arm.
    """
    cap = sum_tree.shape[0] // 2
    levels = int(math.log2(cap))  # jaxlint: disable=host-sync-in-jit (shape: static under jit)
    q = mass.shape[0]
    pad = (-q) % _TILE_Q
    m = jnp.pad(mass.astype(jnp.float32), (0, pad))[:, None]
    total_q = q + pad
    chunk = min(_CHUNK, 2 * cap)  # 2 * cap is a power of two: it divides
    tree = sum_tree.reshape(2 * cap // chunk, chunk)

    kernel = functools.partial(_descent_kernel, levels=levels, cap=cap,
                               chunk=chunk)
    idx = pl.pallas_call(
        kernel,
        grid=(total_q // _TILE_Q,),
        in_specs=[
            pl.BlockSpec(tree.shape, lambda i: (0, 0)),
            pl.BlockSpec((_TILE_Q, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_TILE_Q, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((total_q, 1), jnp.int32),
        # the resident tree block is double-buffered by the pipeline:
        # twice its bytes plus headroom, past the 16 MiB scoped default
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * tree.size * 4 + 16 * 1024 * 1024),
        interpret=interpret,
    )(tree, m)
    return idx[:q, 0]
