"""Causal attention over keys the model chooses at run time: a small scorer
(the indexer of DeepSeek-V3.2-Exp's sparse attention, as Keye-VL-2.0's
``sa_config`` sizes it) ranks every earlier position for every query, and
the main attention reads the ``topk`` best of them and nothing else.

Three steps a sequence, the first and the last a block of ``q_chunk``
queries at a time so that no ``[heads, T, T]`` array is ever made (16,384 x
16,384 float32 is 1 GB a head):

1. ``select_keys``: index scores ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
   kI[s])`` against the keys a block can see, the exact selection
   ``S_t`` (the ``topk`` largest ``I[t, s]``, ``s <= t``; all of them while
   there are fewer; ties to the lower position) as a ``[T, T]`` bool mask,
   and how many selections fell on each ``kv_chunk`` keys. The selection
   carries no gradient.
2. ``masked_attention``: softmax attention under that mask. In the
   differentiated pass ``attention_and_lse``: the same output from the same
   forward call, and with it every head's log-sum-exp over ``S_t``, which
   that call makes as its backward's residual.
3. ``alignment_loss`` (the differentiated pass only): the loss the indexer
   is trained by, ``sum_t KL(p_t || softmax_{S_t} I[t, .])``, ``p_t`` the
   main attention's probabilities over ``S_t`` summed over its heads and
   L1-normalised, a constant: ``exp(q . k - lse)`` under the log-sum-exp
   step 2 hands over, no pass of the main attention of its own. The index
   scores are made again here, with their gradient.

The selection is a threshold, not a sort: ``lax.top_k`` at ``k`` 2,048 of
16,384 is a full sort on the TPU (291 ms a sequence on the v5e against 19
ms, 6 of them the scores; the same set; my chip run, PR 32). Scores become
integers in float order, 32 counting passes find each row's ``topk``-th
largest bit by bit, and a running count of the scores equal to it hands the
places that are left to the lowest positions: exactly ``lax.top_k``'s set.

Blocks of queries are grouped by how far they can see (``GROUPS`` static
key extents, multiples of ``kv_chunk``), so steps 1 and 3 and the blockwise
step 2 touch 9/16 of the square, not all of it, in ``GROUPS`` compiled
bodies.
Neither the selection nor the output depends on ``q_chunk``, ``kv_chunk``
or ``GROUPS``.

``masked_attention`` implementations:

- ``splash``: Pallas kernels of this repo, TPU only (blocks of 512), all
  three built alike: a grid step holds the ``G`` query heads of a key/value
  head (eight in ``humanoid-keye2-ep8``), one tile of keys, one of values
  and ONE int8 tile of the selection for all of them, read from ``keep`` as
  it is (a byte a pair), and the blocks that keep no pair (above the
  diagonal, all of them) are neither fetched nor run; inside the others
  the mask is applied, so every causal block is visited whatever was
  selected. The forward (``group_masked_forward``, since PR 43) hands back
  the output and every head's log-sum-exp, which is both the backward's
  residual and the alignment target's, so one call serves the attention,
  its gradient and the loss, and the passes that need neither drop it. The
  backward (since PR 46) is ``group_masked_dq``, on the forward's grid and
  block table, and ``group_masked_dkv``, queries innermost, which holds a
  tile of keys and of values across the queries and sums ``dk`` and ``dv``
  over the blocks of queries and the ``G`` heads in VMEM; it reads the
  forward's int8 copy of the mask, a tile turned in the kernel for all
  ``G`` heads, by a block table by columns. The arithmetic is that of the splash-attention kernels
  jax ships (0.9.0), step for step; those step one query head at a time
  and read the mask as int32 blocks that ``process_dynamic_mask`` lays out
  (1 GiB a layout at 16,384 tokens: 17.7 GB of mask a call where these
  read 0.55). What is assumed of ``keep``: a bool ``[T, T]`` in which
  every query keeps at least one key; not that it is causal, nor that
  every key is kept. A sequence alone on the v5e (my chip runs, PR 43):
  forward 16.5 ms of which the kernel 14.6-15.2, the int8 copy 1.6 and the
  block table 1.0 (jax's dynamic-mask forward: 35.0 with its layout, 33.9
  without; a static causal mask over the same pairs: 17.3). Blocks of
  1,024 x 512, 512 x 1,024 and 1,024 x 1,024 read within 0.3 ms of 512 x
  512; 256 x 512 is 1.3 ms slower; one int8 tile a head in place of a
  group 21.2-27.1 ms. The backward alone (my chip runs, PR 46): ``dq`` 20.4
  ms and ``dkv`` 25.7, 19.3 and 24.2 a call inside the chunk (90 and 95 %
  of the three and four products a causal block at the chip's peak),
  ``di`` 1.0, where jax's pair took 78.9 with its two layouts (3.7 to
  make); forward and backward 59.9 (93.7). Blocks of 1,024 on either axis read within
  0.3 ms of 512 x 512 in both kernels and 256 is 0.8-1.0 slower; with the
  heads in a ``fori_loop`` (one, two, four a loop step) ``dq`` takes 22.8
  / 21.7 / 21.2 and ``dkv`` 28.2 / 27.1 / 26.5, and the cell's warm
  set-up did not move with the heads unrolled (114.8 s against 115.2), so
  they stay unrolled. ``dq`` is the bits of jax's; ``dk`` and ``dv`` are
  summed in another order (``group_masked_dkv``).
- ``blockwise``: plain ``jax.numpy``, a block of queries against the keys
  its group sees, rematerialised in the backward pass. Runs anywhere (441
  ms forward there).

Shapes: ``q [Hkv, G, T, D]`` already scaled, ``k``, ``v`` ``[Hkv, T, D]``;
``qi [T, Hi, Di]``, ``ki [T, Di]`` (one key head for all ``Hi``), ``wi
[T, Hi]`` float32 with the score's scale factors folded in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

IMPLS = ("splash", "blockwise")
GROUPS = 8
SPLASH_BLOCK = 512
# the kernels' names in a compiled program and a trace, and the fast memory
# each may take: a group's accumulators (6 MB at 8 heads and blocks of 512)
# and the unrolled heads' score tiles are refused under the compiler's
# default of 16 MiB and fit in 32 (the v5e has 128)
FORWARD_KERNEL = "group_masked_fwd"
DQ_KERNEL = "group_masked_dq"
DKV_KERNEL = "group_masked_dkv"
VMEM_LIMIT = 64 * 2 ** 20


def block_plan(t_len: int, q_chunk: int, kv_chunk: int) -> list:
    """``[(first query, query blocks, keys seen)]``: at most ``GROUPS``
    runs of query blocks, each with the static number of keys its last
    query sees, rounded up to ``kv_chunk``."""
    if t_len % q_chunk or t_len % kv_chunk:
        raise ValueError(f"{t_len} positions do not divide into blocks of "
                         f"{q_chunk} queries and {kv_chunk} keys")
    blocks = t_len // q_chunk
    groups = min(GROUPS, blocks)
    plan, first = [], 0
    for g in range(groups):
        n = blocks // groups + (g < blocks % groups)
        end = (first + n) * q_chunk
        plan.append((first * q_chunk, n, -(-end // kv_chunk) * kv_chunk))
        first += n
    return plan


def _by_blocks(fn, plan, q_chunk: int, per_query: tuple, per_key: tuple):
    """``fn(start, query blocks..., keys..., extent=)`` over every block of
    the plan (``lax.map`` inside a group: one compiled body a group), each
    rematerialised on its own in the backward pass. ``per_query`` arrays
    lead with ``T``; ``per_key`` arrays have ``T`` second to last. Returns
    ``(rows, sums)``: ``fn``'s first result concatenated over queries, its
    second summed over blocks."""
    rows, sums = [], None
    for first, n, extent in plan:
        last = first + n * q_chunk
        xs = tuple(a[first:last].reshape((n, q_chunk) + a.shape[1:])
                   for a in per_query)
        keys = tuple(a[..., :extent, :] for a in per_key)
        starts = first + q_chunk * jnp.arange(n, dtype=jnp.int32)
        body = jax.checkpoint(functools.partial(fn, extent=extent))
        out, part = jax.lax.map(
            lambda args, keys=keys, body=body: body(args[0], *args[1],
                                                    *keys), (starts, xs))
        rows.append(out.reshape((n * q_chunk,) + out.shape[2:]))
        part = jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), part)
        sums = part if sums is None else jax.tree_util.tree_map(
            jnp.add, sums, part)
    return jnp.concatenate(rows, axis=0), sums


# -- scores and selection -----------------------------------------------------
def index_scores(qi, ki, wi):
    """``[Tq, S]`` float32 from ``qi [Tq, Hi, Di]``, ``ki [S, Di]``, ``wi
    [Tq, Hi]``."""
    s = jnp.einsum("qhd,kd->qhk", qi, ki, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * wi[:, :, None], axis=1)


def select(scores, causal, topk: int):
    """The exact selection of float32 ``scores [rows, S]`` among the
    positions ``causal [rows, S]`` allows: bool ``[rows, S]`` (module
    docstring)."""
    # -0.0 and 0.0 are one score; then signed integers in float order,
    # then unsigned, with 0 for what may not be chosen (below -inf's key)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, jnp.zeros((), scores.dtype), scores),
        jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    u = jnp.where(causal, jax.lax.bitcast_convert_type(key, jnp.uint32)
                  ^ jnp.uint32(0x80000000), jnp.uint32(0))

    def at_least(v):
        return jnp.sum(u >= v[:, None], axis=-1, dtype=jnp.int32)

    def bit(i, v):
        cand = v | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        return jnp.where(at_least(cand) >= topk, cand, v)

    # the largest value that at least topk scores reach: the topk-th
    # largest (0, which everything reaches, where there are fewer)
    v = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))
    above = u > v[:, None]
    equal = u == v[:, None]
    left = topk - jnp.sum(above, axis=-1, dtype=jnp.int32)
    before = jnp.cumsum(equal, axis=-1, dtype=jnp.int32) - equal.astype(
        jnp.int32)
    return (above | (equal & (before < left[:, None]))) & causal


def _select_block(start, qi, wi, ki, *, extent, topk, t_len, kv_chunk):
    scores = index_scores(qi, ki, wi)
    t = start + jnp.arange(qi.shape[0], dtype=jnp.int32)[:, None]
    causal = jnp.arange(extent, dtype=jnp.int32)[None, :] <= t
    keep = select(scores, causal, topk)
    counts = jnp.sum(keep, axis=0, dtype=jnp.int32).reshape(
        extent // kv_chunk, kv_chunk).sum(axis=-1)
    counts = jnp.pad(counts, (0, (t_len - extent) // kv_chunk))
    return jnp.pad(keep, ((0, 0), (0, t_len - extent))), counts


def select_keys(qi, ki, wi, *, topk: int, q_chunk: int, kv_chunk: int):
    """``(keep [T, T] bool, counts [T / kv_chunk] int32)``: the selection
    of every query, and the selections by block of ``kv_chunk`` keys summed
    over queries. No gradient."""
    qi, ki, wi = jax.lax.stop_gradient((qi, ki, wi))
    t_len = qi.shape[0]
    fn = functools.partial(_select_block, topk=topk, t_len=t_len,
                           kv_chunk=kv_chunk)
    return _by_blocks(fn, block_plan(t_len, q_chunk, kv_chunk), q_chunk,
                      (qi, wi), (ki,))


# -- the indexer's loss -------------------------------------------------------
def _head_mean_probs(q, k, keep):
    """The main attention's probabilities over ``keep [bq, S]``, the mean
    over heads: ``q [Hkv, G, bq, D]`` (scaled), ``k [Hkv, S, D]``. Plain
    ``jnp``, one key/value head's scores at a time."""
    def head(total, xs):
        qh, kh = xs
        s = jnp.einsum("gqd,kd->gqk", qh, kh,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return total + jnp.sum(p, axis=0), None

    p, _ = jax.lax.scan(head, jnp.zeros(keep.shape, jnp.float32), (q, k))
    return p / (q.shape[0] * q.shape[1])


def head_mean_probs_kernel(q, k, lse, keep, *, interpret: bool = False):
    """``_head_mean_probs`` as a Pallas kernel, given every head's
    log-sum-exp over its selection (``lse [Hkv, G, bq]``, the splash
    kernel's own residual): a tile of keys at a time, the heads' ``exp(q .
    k - lse)`` summed in the output tile while it stays in VMEM, so no
    ``[heads, bq, S]`` array reaches HBM (on the v5e a 16,384-token
    sequence's loss takes 462 ms in the ``jnp`` form, 50 ms in this one, 35
    of them a forward pass for the lse, which since PR 42 is the main
    attention's own; my chip run, PR 32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv, group, bq, d = q.shape
    s_len = k.shape[1]
    bkv = math.gcd(SPLASH_BLOCK, s_len)
    lanes = min(128, bkv)
    # a query's lse on every lane, as the splash kernel keeps it
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (lanes,))

    def kernel(q_ref, k_ref, lse_ref, keep_ref, out_ref):
        head = pl.program_id(1)

        # (a Pallas kernel writes its output through the ref it is given)
        @pl.when(head == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)  # jaxlint: disable=tracer-leak

        keys = k_ref[0]
        total = out_ref[...]
        for g in range(group):
            logits = jax.lax.dot_general(
                q_ref[0, g], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            # a selected score never exceeds its row's lse; what is not
            # selected is dropped below, and must not overflow before
            total += jnp.exp(jnp.minimum(
                logits - pltpu.repeat(lse_ref[0, g], bkv // lanes, axis=1),
                0.0))
        out_ref[...] = total

        @pl.when(head == hkv - 1)
        def _():
            # the kernel's output ref again, not a leak
            out_ref[...] = jnp.where(  # jaxlint: disable=tracer-leak
                keep_ref[...], out_ref[...] * (1.0 / (hkv * group)), 0.0)

    return pl.pallas_call(
        kernel, grid=(s_len // bkv, hkv),
        in_specs=[
            pl.BlockSpec((1, group, bq, d), lambda j, h: (h, 0, 0, 0)),
            pl.BlockSpec((1, bkv, d), lambda j, h: (h, j, 0)),
            pl.BlockSpec((1, group, bq, lanes), lambda j, h: (h, 0, 0, 0)),
            pl.BlockSpec((bq, bkv), lambda j, h: (0, j))],
        out_specs=pl.BlockSpec((bq, bkv), lambda j, h: (0, j)),
        out_shape=jax.ShapeDtypeStruct((bq, s_len), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="head_mean_probs")(q, k, lse, keep)


def _align_block(_start, qi, wi, keep, q, lse, ki, k, *, extent, interpret):
    """``sum_t KL(p_t || softmax_{S_t} I[t, .])`` of one block of queries:
    ``q [bq, Hkv, G, D]``, ``lse [bq, Hkv, G]`` or ``None`` (the ``jnp``
    form)."""
    scores = index_scores(qi, ki, wi)
    keep = keep[:, :extent]
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    q = jnp.moveaxis(q, 0, 2)
    p = (_head_mean_probs(q, k, keep) if lse is None
         else head_mean_probs_kernel(q, k, jnp.moveaxis(lse, 0, 2), keep,
                                     interpret=interpret))
    p = jax.lax.stop_gradient(p)
    log_p = jnp.log(jnp.where(p > 0, p, 1.0))
    loss = jnp.sum(p * (log_p - jnp.where(keep, log_q, 0.0)))
    return jnp.zeros((qi.shape[0], 0), jnp.float32), loss


def alignment_loss(qi, ki, wi, keep, q, k, lse, *, q_chunk: int,
                   kv_chunk: int, interpret: bool = False):
    """The indexer's loss summed over the queries of a sequence: the KL
    divergence from the main attention's probabilities over the selection
    (the mean over heads, a constant) to ``softmax_{S_t} I[t, .]``.
    Gradient reaches ``qi``, ``ki``, ``wi`` and nothing else. ``lse [Hkv,
    G, T]`` is every head's log-sum-exp over its selection as the main
    attention's own forward pass made it (``attention_and_lse``): the
    heads are then summed in ``head_mean_probs_kernel``; with ``None``
    the target is plain ``jnp``."""
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    plan = block_plan(qi.shape[0], q_chunk, kv_chunk)
    per_query = (qi, wi, keep, jnp.moveaxis(q, 2, 0))
    if lse is not None:
        fn = functools.partial(_align_block, interpret=interpret)
        per_query += (jnp.moveaxis(lse, 2, 0),)
    else:
        fn = lambda start, qi, wi, keep, q, ki, k, *, extent: (  # noqa: E731
            _align_block(start, qi, wi, keep, q, None, ki, k, extent=extent,
                         interpret=False))
    _, loss = _by_blocks(fn, plan, q_chunk, per_query, (ki, k))
    return loss


# -- attention under the selection --------------------------------------------
def _attend_block(_start, q, keep, k, v, *, extent):
    """``q [bq, Hkv, G, D]`` against ``k, v [Hkv, extent, D]``."""
    s = jnp.einsum("qhgd,hkd->hgqk", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(keep[None, None, :, :extent], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hgqk,hkd->qhgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), ()


def blockwise_masked_attention(q, k, v, keep, *, q_chunk: int,
                               kv_chunk: int):
    plan = block_plan(q.shape[2], q_chunk, kv_chunk)
    out, _ = _by_blocks(_attend_block, plan, q_chunk,
                        (jnp.moveaxis(q, 2, 0), keep), (k, v))
    return jnp.moveaxis(out, 0, 2)


def splash_fits(t_len: int, head_dim: int, kv_chunk: int) -> bool:
    """Whether the kernels' tiling takes these sizes (a group's extent of
    keys is a multiple of ``kv_chunk``)."""
    return (t_len % SPLASH_BLOCK == 0 and head_dim % 128 == 0
            and kv_chunk % 128 == 0)


def _kept_blocks(keep, bq: int, bkv: int):
    """``[T / bq, T / bkv]`` bool: the blocks of ``keep`` that keep a
    pair."""
    t_len = keep.shape[0]
    # down the rows first, then along them: 0.96 ms on the v5e at 16,384
    # positions where both axes at once take 1.93 (my chip run, PR 43)
    some = jnp.any(keep.reshape(t_len // bq, bq, t_len), axis=1)
    return jnp.any(some.reshape(t_len // bq, t_len // bkv, bkv), axis=2)


def _block_table(some):
    """``[rows, steps]`` int32 from the kept blocks ``some [rows, steps]``,
    a block for every grid step of a kernel that walks a row of them to
    hold: the step's own where the block keeps a pair (the step runs),
    else the last one before it that does (the first, before any), which
    the step before holds already, so nothing is fetched and nothing run.
    ``group_masked_forward`` and ``group_masked_dq`` walk the keys of a
    block of queries (``some`` as ``_kept_blocks`` makes it),
    ``group_masked_dkv`` the queries of a block of keys (its transpose)."""
    j = jnp.arange(some.shape[1], dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(some, j, -1), axis=1)
    first = jnp.argmax(some, axis=1).astype(jnp.int32)
    return jnp.where(last >= 0, last, first[:, None])


def _blocks(t_len: int, block_q: int | None, block_kv: int | None):
    return (min(block_q or SPLASH_BLOCK, t_len),
            min(block_kv or SPLASH_BLOCK, t_len))


def _wide(a, n: int):
    """A row's value on every lane of ``a [rows, lanes]``, on ``n`` lanes."""
    lanes = a.shape[-1]
    return a if n == lanes else jnp.tile(a, (1, n // lanes))


def _mask_value():
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sa)
    return sa.DEFAULT_MASK_VALUE


def _group_call(kernel, name: str, *, grid, table, in_specs, out_specs,
                out_shape, scratch, operands, interpret: bool):
    """One of this module's kernels over ``grid`` (key/value heads first,
    then the axis a step holds still, then the axis it walks by
    ``table``), under its name in a compiled program and a trace."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental import pallas as pl

    # (the scope keeps the call's name in a compiled program the kernel's
    # own under a transformation too, as jax's kernels keep theirs)
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret, name=name)(table, *operands)


def _forward(q, k, v, keep, block_q, block_kv, interpret: bool):
    """``group_masked_forward``'s ``(out, lse)``, and what it made of
    ``keep`` that the backward reads again: the int8 copy and the kept
    blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv, group, t_len, d = q.shape
    bq, bkv = _blocks(t_len, block_q, block_kv)
    kept, some = keep.astype(jnp.int8), _kept_blocks(keep, bq, bkv)
    lanes = min(128, bkv)
    steps = t_len // bkv
    mask_value = _mask_value()

    # (the steps take the refs they write as arguments: a Pallas kernel
    # hands its outputs and scratch back through them)
    def start(m_ref, l_ref, acc_ref):
        m_ref[...] = jnp.full_like(m_ref, mask_value)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def visit(q_ref, k_ref, v_ref, keep_ref, m_ref, l_ref, acc_ref):
        kept = keep_ref[...].astype(jnp.int32) != 0
        keys, values = k_ref[0], v_ref[0]
        for g in range(group):
            s = jax.lax.dot_general(
                q_ref[0, g], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = jnp.where(kept, s, mask_value)
            m_prev, l_prev = m_ref[g], l_ref[g]
            m_next = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
            p = jnp.exp(s - _wide(m_next, bkv))
            alpha = jnp.exp(m_prev - m_next)
            m_ref[g] = m_next
            l_ref[g] = alpha * l_prev + jax.lax.broadcast_in_dim(
                p.sum(axis=-1), l_prev.shape, (0,))
            acc_ref[g] = _wide(alpha, d) * acc_ref[g] + jax.lax.dot_general(
                p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    def finish(out_ref, lse_ref, m_ref, l_ref, acc_ref):
        for g in range(group):
            l = l_ref[g]
            out_ref[0, g] = (acc_ref[g] * _wide(1.0 / l, d)).astype(
                out_ref.dtype)
            # a query's value fills its row of lanes: one column of it,
            # with the queries along the lanes
            lse_ref[0, pl.ds(g, 1), :] = jnp.transpose(
                jnp.log(l) + m_ref[g])[:1]

    def kernel(table_ref, q_ref, k_ref, v_ref, keep_ref, out_ref, lse_ref,
               *scratch):
        i, j = pl.program_id(1), pl.program_id(2)
        pl.when(j == 0)(lambda: start(*scratch))
        pl.when(table_ref[i, j] == j)(
            lambda: visit(q_ref, k_ref, v_ref, keep_ref, *scratch))
        pl.when(j == steps - 1)(lambda: finish(out_ref, lse_ref, *scratch))

    heads = pl.BlockSpec((1, group, bq, d), lambda h, i, j, t: (h, 0, i, 0))
    keys = pl.BlockSpec((1, bkv, d), lambda h, i, j, t: (h, t[i, j], 0))
    return _group_call(
        kernel, FORWARD_KERNEL, grid=(hkv, t_len // bq, steps),
        table=_block_table(some),
        in_specs=[heads, keys, keys,
                  pl.BlockSpec((bq, bkv), lambda h, i, j, t: (i, t[i, j]))],
        out_specs=[heads, pl.BlockSpec((1, group, bq),
                                       lambda h, i, j, t: (h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(q.shape[:3], jnp.float32)],
        scratch=[pltpu.VMEM((group, bq, lanes), jnp.float32),
                 pltpu.VMEM((group, bq, lanes), jnp.float32),
                 pltpu.VMEM((group, bq, d), jnp.float32)],
        operands=(q, k, v, kept), interpret=interpret), (kept, some)


def group_masked_forward(q, k, v, keep, *, block_q: int | None = None,
                         block_kv: int | None = None,
                         interpret: bool = False):
    """``(out [Hkv, G, T, D], lse [Hkv, G, T] float32)``: softmax attention
    under ``keep [T, T]`` and every query head's log-sum-exp over its
    selection, the forward pass as a Pallas kernel of this repo. One grid
    step holds a key/value head's ``G`` tiles of queries, one tile of keys,
    one of values and ONE tile of ``keep`` as int8 for all ``G`` heads,
    which are taken in a loop (jax 0.9.0's kernel steps a query head at a
    time and reads the mask as int32: eight fetches of 1 MB where this
    makes one of 256 KB). Blocks that keep no pair are neither fetched nor
    run (``_block_table``); ``keep`` is any bool matrix in which every
    query keeps a key, causal or not. The arithmetic is jax's kernel's,
    step for step: products of the inputs' dtype summed in float32, the
    softmax in float32 under a running maximum, ``DEFAULT_MASK_VALUE`` for
    what is not kept, ``lse = m + log(l)``."""
    return _forward(q, k, v, keep, block_q, block_kv, interpret)[0]


def group_masked_dq(q, k, v, kept, table, lse, di, d_out, *,
                    block_q: int | None = None, block_kv: int | None = None,
                    interpret: bool = False):
    """``dq [Hkv, G, T, D]``: the queries' gradient of attention under the
    mask whose int8 copy is ``kept [T, T]``, from the forward's ``lse``,
    ``di = sum(out * d_out, -1)`` (both ``[Hkv, G, T]`` float32) and the
    output's cotangent. The forward's grid and the forward's block table
    (``table``, by rows): a step holds the group's tiles of ``q``,
    ``d_out``, ``lse`` and ``di`` across the keys, streams one tile of
    keys, one of values and one int8 tile of the mask for all ``G`` heads,
    and sums ``dq`` in float32 in VMEM, written at the last block of keys.
    jax's ``dq`` kernel's arithmetic, step for step: ``p = exp(s - lse)``
    under ``DEFAULT_MASK_VALUE``, ``ds = (dp - di) p``, ``ds`` cast to the
    inputs' dtype before its product, the sums in the same order (the same
    bits)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv, group, t_len, d = q.shape
    bq, bkv = _blocks(t_len, block_q, block_kv)
    lanes = min(128, bkv)
    steps = t_len // bkv
    mask_value = _mask_value()

    def start(lse_ref, di_ref, dq_acc, lse_rows, di_rows):
        dq_acc[...] = jnp.zeros_like(dq_acc)
        # the queries lie along the lanes in ``lse`` and ``di`` and down
        # the rows of a score tile: turned once a block of queries
        for g in range(group):
            for ref, rows in ((lse_ref, lse_rows), (di_ref, di_rows)):
                rows[g] = jnp.transpose(jnp.broadcast_to(
                    ref[0, pl.ds(g, 1), :], (lanes, bq)))

    def visit(q_ref, do_ref, k_ref, v_ref, keep_ref, dq_acc, lse_rows,
              di_rows):
        kept = keep_ref[...].astype(jnp.int32) != 0
        keys, values = k_ref[0], v_ref[0]
        for g in range(group):
            s = jax.lax.dot_general(
                q_ref[0, g], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(kept, s, mask_value)
                        - _wide(lse_rows[g], bkv))
            dp = jax.lax.dot_general(
                do_ref[0, g].astype(values.dtype), values,
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            ds = (dp - _wide(di_rows[g], bkv)) * p
            dq_acc[g] += jax.lax.dot_general(
                ds.astype(keys.dtype), keys, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    def finish(dq_ref, dq_acc):
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)

    def kernel(table_ref, q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
               keep_ref, dq_ref, dq_acc, lse_rows, di_rows):
        i, j = pl.program_id(1), pl.program_id(2)
        pl.when(j == 0)(lambda: start(lse_ref, di_ref, dq_acc, lse_rows,
                                      di_rows))
        pl.when(table_ref[i, j] == j)(
            lambda: visit(q_ref, do_ref, k_ref, v_ref, keep_ref, dq_acc,
                          lse_rows, di_rows))
        pl.when(j == steps - 1)(lambda: finish(dq_ref, dq_acc))

    heads = pl.BlockSpec((1, group, bq, d), lambda h, i, j, t: (h, 0, i, 0))
    rows = pl.BlockSpec((1, group, bq), lambda h, i, j, t: (h, 0, i))
    keys = pl.BlockSpec((1, bkv, d), lambda h, i, j, t: (h, t[i, j], 0))
    return _group_call(
        kernel, DQ_KERNEL, grid=(hkv, t_len // bq, steps), table=table,
        in_specs=[heads, heads, rows, rows, keys, keys,
                  pl.BlockSpec((bq, bkv), lambda h, i, j, t: (i, t[i, j]))],
        out_specs=heads, out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch=[pltpu.VMEM((group, bq, d), jnp.float32),
                 pltpu.VMEM((group, bq, lanes), jnp.float32),
                 pltpu.VMEM((group, bq, lanes), jnp.float32)],
        operands=(q, d_out, lse, di, k, v, kept), interpret=interpret)


def group_masked_dkv(q, k, v, kept, table, lse, di, d_out, *,
                     block_q: int | None = None,
                     block_kv: int | None = None, interpret: bool = False):
    """``(dk, dv) [Hkv, T, D]``: the keys' and values' gradients, queries
    innermost: grid ``(Hkv, T / bkv, T / bq)``. A step holds one tile of
    keys and one of values across the queries (fetched once a block of
    keys, not once a head and block), streams the group's tiles of ``q``,
    ``d_out``, ``lse``, ``di`` and one int8 tile of the mask by the block
    table by columns (``table [T / bkv, T / bq]``), and sums ``dk`` and
    ``dv`` in float32 in VMEM over the blocks of queries AND the ``G``
    heads, written at the last block of queries: multi-query's sum over
    heads is that accumulation, and no ``[G, ...]`` partial reaches HBM.
    The score tiles stand keys by queries, as in jax's ``dkv`` kernel, so
    every product is one the MXU takes as it is and ``lse`` and ``di``
    are read as they lie (queries along the lanes); the mask's tile is the
    forward's (``kept``, queries by keys), turned in the kernel once a
    step for all ``G`` heads (a second copy of the mask by keys, made by
    XLA, reads 0.1 ms slower a call and costs 1.3-1.8 ms and 268 MB to
    make; my chip run, PR 46). jax's kernel's arithmetic, step for step; it
    sums a head's blocks of queries and then the next head's, this a
    block's heads and then the next block's: the same terms in float32 in
    another order (on the chip 0.03 % of the bfloat16 elements of ``dk``
    and of ``dv`` differ, by at most 2^-7 on values up to 6.5 and 14.75). A
    block of keys that no query keeps runs once, on a tile that masks
    everything: ``p`` is 0 there and so are its sums."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hkv, group, t_len, d = q.shape
    bq, bkv = _blocks(t_len, block_q, block_kv)
    steps = t_len // bq
    mask_value = _mask_value()

    def start(dk_acc, dv_acc):
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def visit(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref, keep_ref, dk_acc,
              dv_acc):
        kept = jnp.transpose(keep_ref[...].astype(jnp.float32)) != 0
        keys, values = k_ref[0], v_ref[0]
        for g in range(group):
            queries, do = q_ref[0, g], do_ref[0, g]
            s = jax.lax.dot_general(
                keys, queries, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(kept, s, mask_value)
                        - lse_ref[0, pl.ds(g, 1), :])
            dv_acc[...] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                values, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = (dp - di_ref[0, pl.ds(g, 1), :]) * p
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(do.dtype), queries, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    def finish(dk_ref, dv_ref, dk_acc, dv_acc):
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    def kernel(table_ref, q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
               keep_ref, dk_ref, dv_ref, dk_acc, dv_acc):
        j, i = pl.program_id(1), pl.program_id(2)
        pl.when(i == 0)(lambda: start(dk_acc, dv_acc))
        pl.when(table_ref[j, i] == i)(
            lambda: visit(q_ref, do_ref, lse_ref, di_ref, k_ref, v_ref,
                          keep_ref, dk_acc, dv_acc))
        pl.when(i == steps - 1)(
            lambda: finish(dk_ref, dv_ref, dk_acc, dv_acc))

    heads = pl.BlockSpec((1, group, bq, d),
                         lambda h, j, i, t: (h, 0, t[j, i], 0))
    rows = pl.BlockSpec((1, group, bq), lambda h, j, i, t: (h, 0, t[j, i]))
    keys = pl.BlockSpec((1, bkv, d), lambda h, j, i, t: (h, j, 0))
    return _group_call(
        kernel, DKV_KERNEL, grid=(hkv, t_len // bkv, steps), table=table,
        in_specs=[heads, heads, rows, rows, keys, keys,
                  pl.BlockSpec((bq, bkv), lambda h, j, i, t: (t[j, i], j))],
        out_specs=[keys, keys],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch=[pltpu.VMEM((bkv, d), jnp.float32),
                 pltpu.VMEM((bkv, d), jnp.float32)],
        operands=(q, d_out, lse, di, k, v, kept), interpret=interpret)


# Forward and backward are this module's kernels. The forward's int8 copy
# of ``keep`` and its kept blocks ride to the backward rule, which makes of
# the blocks ``group_masked_dkv``'s table by columns: no program lays the
# mask out as int32 or copies it a second time
# (``tests/test_torso_v5e_compile.py`` compiles all three kernels).
# ``keep`` is any bool ``[T, T]`` in which every query keeps a key; no
# kernel assumes the causal order.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _splash_out_and_lse(interpret, keep, q, k, v):
    return group_masked_forward(q, k, v, keep, interpret=interpret)


def _splash_out_and_lse_fwd(interpret, *primals):
    from jax.custom_derivatives import CustomVJPPrimal

    keep, q, k, v = jax.tree_util.tree_map(
        lambda p: p.value, primals,
        is_leaf=lambda p: isinstance(p, CustomVJPPrimal))
    (out, lse), (kept, some) = _forward(q, k, v, keep, None, None, interpret)
    return (out, lse), (q, k, v, out, lse, kept, some)


def _splash_out_and_lse_bwd(interpret, res, cts):
    from jax.custom_derivatives import SymbolicZero

    q, k, v, out, lse, kept, some = res
    d_out, d_lse = cts
    if not isinstance(d_lse, SymbolicZero):
        raise TypeError("the log-sum-exp is handed out as a constant: its "
                        "backward is not the kernel's (stop_gradient it)")
    # as jax's backward makes it: float32, plain XLA, once a call
    di = jnp.einsum("hgsd,hgsd->hgs", out.astype(jnp.float32),
                    d_out.astype(jnp.float32))
    dq = group_masked_dq(q, k, v, kept, _block_table(some), lse, di, d_out,
                         interpret=interpret)
    dk, dv = group_masked_dkv(q, k, v, kept, _block_table(some.T), lse, di,
                              d_out, interpret=interpret)
    return None, dq, dk, dv


_splash_out_and_lse.defvjp(_splash_out_and_lse_fwd, _splash_out_and_lse_bwd,
                           symbolic_zeros=True)


def splash_attention_and_lse(q, k, v, keep, *, interpret: bool = False):
    """Attention under ``keep`` by the kernels, each key/value head with
    its ``G`` query heads: the output, differentiable (this module's
    forward, ``dq`` and ``dkv``), and ``lse [Hkv, G, T]`` float32, every
    query head's log-sum-exp over its selection, from the one forward call
    (a constant: the backward rule refuses a cotangent on it)."""
    out, lse = _splash_out_and_lse(interpret, keep, q, k, v)
    return out, jax.lax.stop_gradient(lse)


def splash_masked_attention(q, k, v, keep, *, interpret: bool = False):
    """``splash_attention_and_lse``'s output alone: the same forward call
    with its log-sum-exp dropped (the passes that keep no residual)."""
    return splash_attention_and_lse(q, k, v, keep, interpret=interpret)[0]


def masked_attention(q, k, v, keep, *, impl: str, q_chunk: int,
                     kv_chunk: int):
    """Softmax attention of ``q [Hkv, G, T, D]`` over the positions ``keep
    [T, T]`` allows each query (every row keeps at least its own)."""
    if impl not in IMPLS:
        raise ValueError(f"unknown attention impl {impl!r}; one of {IMPLS}")
    if impl == "splash":
        return splash_masked_attention(q, k, v, keep)
    return blockwise_masked_attention(q, k, v, keep, q_chunk=q_chunk,
                                      kv_chunk=kv_chunk)


def attention_and_lse(q, k, v, keep, *, impl: str, q_chunk: int,
                      kv_chunk: int):
    """``masked_attention`` for the pass that also trains the indexer:
    ``(out, lse)``, ``lse`` what ``alignment_loss`` takes (``None`` from
    ``blockwise``, whose loss makes its own target)."""
    if impl == "splash":
        return splash_attention_and_lse(q, k, v, keep)
    return masked_attention(q, k, v, keep, impl=impl, q_chunk=q_chunk,
                            kv_chunk=kv_chunk), None
