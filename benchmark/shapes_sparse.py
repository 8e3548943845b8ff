"""Operations and bytes the sparse-attention torso's layers need in one
gradient step, from the configuration's sizes and what the program counted.
They feed ``indexer_roofline``, ``attn_sparse_roofline`` and
``sparse_experts_roofline`` and live with the benchmark so that no later PR
can move them.

Counting rule (``shapes_torso.py``'s): a multiply-add is 2 FLOPs; only what
the algorithm needs; three forward passes and one backward of two products
a product, five forward-equivalents a step; nothing made again in the
backward pass is counted.

- indexer (scope ``torso.indexer``): the three index projections, and the
  index scores (``indexer_num_heads`` products of ``indexer_head_dim``) over
  the causal pairs: every earlier position has to be scored before any can
  be discarded. The selection itself (comparisons, no products) and the
  alignment target's main-attention scores (made a second time for the
  loss; the kernel does not give its probabilities up) are not counted, so
  the time they take lowers the share.
- attention (scope ``torso.attn_sparse``): the four main projections, and
  ``q k^T`` and ``p v`` over the **selected** pairs only: ``min(t + 1,
  topk)`` a query. A form that visits every causal block and masks reads at
  most ``kept / causal`` of what a dense causal kernel would.
- experts (scope ``torso.experts``): ``shapes_torso.expert_counts`` on the
  assignments the chunk's ``route_counts`` gave the held experts.
"""

from __future__ import annotations

import numpy as np

from benchmark.shapes_torso import (  # noqa: F401 - the expert layer's
    BF16,
    PASSES,
    expert_counts,
    held_assignments,
    load_max_over_mean,
)


def causal_pairs(t_len: int) -> int:
    return t_len * (t_len + 1) // 2


def kept_pairs(t_len: int, topk: int) -> int:
    """Query-key pairs the selection keeps: all of a query's while it has
    at most ``topk``, ``topk`` after."""
    t = np.arange(1, t_len + 1, dtype=np.int64)
    return int(np.sum(np.minimum(t, topk)))


def _sparse_layers(t: dict) -> int:
    return sum(lt == "sparse_attention" for lt in t["layer_types"])


def indexer_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the indexers of all layers."""
    d, t_len, sa = int(t["hidden_size"]), int(t["tokens"]), t["sa_config"]
    hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
    weights = d * (hi * di + di + hi)
    flops = 2.0 * batch * (t_len * weights + causal_pairs(t_len) * hi * di)
    # the bfloat16 matrices once a pass; the normed input in, qI, kI and w
    # once a sequence, and one int32 a selected pair out
    bytes_ = BF16 * weights + batch * (
        t_len * (BF16 * (d + hi * di + di) + 4 * hi)
        + 4 * kept_pairs(t_len, int(sa["topk"])))
    n = _sparse_layers(t)
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def attention_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the main attention of all sparse
    layers, over selected pairs."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    heads, dh = int(t["num_attention_heads"]), int(t["head_dim"])
    hq, hkv = heads * dh, int(t["num_key_value_heads"]) * dh
    proj = t_len * (2 * d * hq + 2 * d * hkv)  # q, o and k, v
    pairs = kept_pairs(t_len, int(t["sa_config"]["topk"])) * heads * dh * 2
    flops = 2.0 * batch * (proj + pairs)
    # as shapes_torso: the bfloat16 matrices once a pass; the float32
    # residual stream in and out and q, k, v, the output once a sequence
    bytes_ = BF16 * (2 * d * hq + 2 * d * hkv) \
        + batch * t_len * (2 * 4 * d + BF16 * 2 * (hq + hkv))
    n = _sparse_layers(t)
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def kept_share(t: dict, select_counts, batch: int) -> float:
    """Percent of the causal pairs that were selected: the mean over steps
    and layers of ``select_counts [K, layers, blocks]`` (summed over the
    batch's sequences by the program)."""
    counts = np.asarray(select_counts, np.float64)
    return float(100.0 * np.mean(np.sum(counts, axis=-1))
                 / (batch * causal_pairs(int(t["tokens"]))))
