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

- ``splash``: Pallas kernels, TPU only (blocks of 512). The forward pass
  is this repo's (``group_masked_forward``, since PR 43): a grid step holds
  the ``G`` query heads of a key/value head (eight in ``humanoid-keye2-
  ep8``), one tile of keys, one of values and ONE int8 tile of the
  selection for all of them, read from ``keep`` as it is (a byte a pair).
  It hands back the output and every head's log-sum-exp, which is both the
  backward's residual and the alignment target's, so one call serves the
  attention, its gradient and the loss, and the passes that need neither
  drop it. The backward is the splash-attention kernels jax ships (0.9.0;
  dq and dkv apart, reached through the private
  ``_splash_attention_bwd``), in their dynamic-mask form:
  ``process_dynamic_mask`` would lay the mask out once a query head; the
  selection is one for all heads, so its blocks are laid out once, by
  query and by key, as int32, and every head's block table points at them.
  Both halves skip the blocks that keep no pair (above the diagonal, all of
  them) and mask inside the others: they visit every causal block whatever
  was selected. What is assumed of ``keep``: a bool ``[T, T]`` in which
  every query keeps at least one key; not that it is causal. A sequence
  alone on the v5e (my chip runs, PR 43): forward 16.5 ms of which the
  kernel 14.6-15.2, the int8 copy 1.6 and the block table 1.0 (jax's
  dynamic-mask forward: 35.0 with its layout, 33.9 without; it steps a head
  at a time and reads the mask as int32, 17.7 GB a call; a static causal
  mask over the same pairs: 17.3); forward and backward 93.8 (110 with
  jax's forward). Blocks of 1,024 x 512, 512 x 1,024 and 1,024 x 1,024
  read within 0.3 ms of 512 x 512; 256 x 512 is 1.3 ms slower; one int8
  tile a head in place of a group 21.2-27.1 ms.
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
# the forward kernel's name in a compiled program and a trace, and the fast
# memory it may take: a group's accumulators (6 MB at 8 heads and blocks of
# 512) and the unrolled heads' score tiles are refused under the compiler's
# default of 16 MiB and fit in 32 (the v5e has 128)
FORWARD_KERNEL = "group_masked_fwd"
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


def _block_table(keep, bq: int, bkv: int):
    """``[T / bq, T / bkv]`` int32, a block of keys for every grid step of
    ``group_masked_forward`` to hold: the step's own where the block keeps
    a pair (the step runs), else the last one before it that does (the
    first, before any), which the step before holds already, so nothing is
    fetched and nothing run."""
    t_len = keep.shape[0]
    # down the rows first, then along them: 0.96 ms on the v5e at 16,384
    # positions where both axes at once take 1.93 (my chip run, PR 43)
    some = jnp.any(keep.reshape(t_len // bq, bq, t_len), axis=1)
    some = jnp.any(some.reshape(t_len // bq, t_len // bkv, bkv), axis=2)
    j = jnp.arange(t_len // bkv, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(some, j, -1), axis=1)
    first = jnp.argmax(some, axis=1).astype(jnp.int32)
    return jnp.where(last >= 0, last, first[:, None])


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
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sa)

    hkv, group, t_len, d = q.shape
    bq = min(block_q or SPLASH_BLOCK, t_len)
    bkv = min(block_kv or SPLASH_BLOCK, t_len)
    lanes = min(128, bkv)
    steps = t_len // bkv
    mask_value = sa.DEFAULT_MASK_VALUE

    def wide(a, n):  # a row's value on ``lanes`` lanes, on ``n`` of them
        return a if n == lanes else jnp.tile(a, (1, n // lanes))

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
            p = jnp.exp(s - wide(m_next, bkv))
            alpha = jnp.exp(m_prev - m_next)
            m_ref[g] = m_next
            l_ref[g] = alpha * l_prev + jax.lax.broadcast_in_dim(
                p.sum(axis=-1), l_prev.shape, (0,))
            acc_ref[g] = wide(alpha, d) * acc_ref[g] + jax.lax.dot_general(
                p.astype(values.dtype), values, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    def finish(out_ref, lse_ref, m_ref, l_ref, acc_ref):
        for g in range(group):
            l = l_ref[g]
            out_ref[0, g] = (acc_ref[g] * wide(1.0 / l, d)).astype(
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

    # (the scope keeps the call's name in a compiled program the kernel's
    # own under a transformation too, as jax's kernels keep theirs)
    with jax.named_scope(FORWARD_KERNEL):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(hkv, t_len // bq, steps),
                in_specs=[
                    pl.BlockSpec((1, group, bq, d),
                                 lambda h, i, j, t: (h, 0, i, 0)),
                    pl.BlockSpec((1, bkv, d),
                                 lambda h, i, j, t: (h, t[i, j], 0)),
                    pl.BlockSpec((1, bkv, d),
                                 lambda h, i, j, t: (h, t[i, j], 0)),
                    pl.BlockSpec((bq, bkv),
                                 lambda h, i, j, t: (i, t[i, j]))],
                out_specs=[
                    pl.BlockSpec((1, group, bq, d),
                                 lambda h, i, j, t: (h, 0, i, 0)),
                    pl.BlockSpec((1, group, bq),
                                 lambda h, i, j, t: (h, 0, i))],
                scratch_shapes=[
                    pltpu.VMEM((group, bq, lanes), jnp.float32),
                    pltpu.VMEM((group, bq, lanes), jnp.float32),
                    pltpu.VMEM((group, bq, d), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                       jax.ShapeDtypeStruct(q.shape[:3], jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret, name=FORWARD_KERNEL)(
                _block_table(keep, bq, bkv), q, k, v, keep.astype(jnp.int8))


def _shared_mask_info(keep, heads: int, block: int, dkv: bool):
    """``keep``'s ``MaskInfo`` for ``heads`` query heads that share it: the
    mask's blocks laid out once (as the kernels take them: ``[blocks,
    block, block]``), every head's tables pointing at them."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask_info as mask_info)

    process = (mask_info.process_dynamic_mask_dkv if dkv
               else mask_info.process_dynamic_mask)
    info, _ = process(keep[None], (block, block), downcast_smem_data=True,
                      head_shards=1, q_seq_shards=1)
    every = lambda a: jnp.broadcast_to(a, (heads,) + a.shape[1:])  # noqa
    return info._replace(
        data_next=every(info.data_next), mask_next=every(info.mask_next),
        block_mask=every(info.block_mask),
        partial_mask_blocks=info.partial_mask_blocks.reshape(
            -1, block, block))


def _backward_layout(keep, group: int, t_len: int):
    """``(block sizes, keep's MaskInfo by query, by key)``: what jax's
    backward kernels of one key/value head and its ``group`` query heads
    take (by query: dq; by key: dkv)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sa)

    b = min(SPLASH_BLOCK, t_len)
    # the fused backward hands dq back once a block of keys ([T / b, G, T,
    # D] a key/value head: 4 GB at 16,384 tokens); dq and dkv apart do not
    sizes = sa.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    return (sizes, _shared_mask_info(keep, group, b, False),
            _shared_mask_info(keep, group, b, True))


# The forward pass is this module's kernel (``group_masked_forward``); the
# backward is jax 0.9.0's: ``_splash_attention_bwd``, a private name of its
# splash-attention module, runs its dq and dkv kernels on ``(q, k, v, out,
# lse)`` and the two int32 layouts of ``keep`` that ``_backward_layout``
# makes (``tests/test_torso_v5e_compile.py`` compiles both halves). The
# layouts only ride to the backward rule: a program that is not
# differentiated never makes them. ``keep`` is any bool ``[T, T]`` in which
# every query keeps a key; neither half assumes the causal order.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _splash_out_and_lse(sizes, interpret, keep, by_query, by_key, q, k, v):
    return group_masked_forward(q, k, v, keep, interpret=interpret)


def _splash_out_and_lse_fwd(sizes, interpret, *primals):
    from jax.custom_derivatives import CustomVJPPrimal

    keep, by_query, by_key, q, k, v = jax.tree_util.tree_map(
        lambda p: p.value, primals,
        is_leaf=lambda p: isinstance(p, CustomVJPPrimal))
    out, lse = _splash_out_and_lse(sizes, interpret, keep, by_query, by_key,
                                   q, k, v)
    return (out, lse), (q, k, v, out, lse, by_query, by_key)


def _splash_out_and_lse_bwd(sizes, interpret, res, cts):
    from jax.custom_derivatives import SymbolicZero
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sa)

    q, k, v, out, lse, by_query, by_key = res
    d_out, d_lse = cts
    if not isinstance(d_lse, SymbolicZero):
        raise TypeError("the log-sum-exp is handed out as a constant: its "
                        "backward is not the kernel's (stop_gradient it)")

    def head(q, k, v, out, lse, d_out):
        return sa._splash_attention_bwd(
            False, sa.DEFAULT_MASK_VALUE, True, sizes, None, None, None,
            interpret, (q, k, v, None, None, out, lse, by_query, by_key),
            d_out)[3:6]

    return (None, None, None) + tuple(
        jax.vmap(head)(q, k, v, out, lse, d_out))


_splash_out_and_lse.defvjp(_splash_out_and_lse_fwd, _splash_out_and_lse_bwd,
                           symbolic_zeros=True)


def splash_attention_and_lse(q, k, v, keep, *, interpret: bool = False):
    """Attention under ``keep`` by the kernels, each key/value head with
    its ``G`` query heads: the output, differentiable (this module's
    forward, jax's dq and dkv), and ``lse [Hkv, G, T]`` float32, every
    query head's log-sum-exp over its selection, from the one forward call
    (a constant: the backward rule refuses a cotangent on it)."""
    sizes, by_query, by_key = _backward_layout(keep, q.shape[1], q.shape[2])
    out, lse = _splash_out_and_lse(sizes, interpret, keep, by_query, by_key,
                                   q, k, v)
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
