"""Operations and bytes the Trinity-Mini torso's layers need in one gradient
step, from the configuration's sizes and the routing the program counted. They
feed ``mix_attn_window_roofline``, ``mix_attn_full_roofline``,
``mix_experts_roofline`` and ``mix_step_mfu`` and live with the benchmark so
that no later PR can move them.

Counting rule (``shapes_torso.py``'s): a multiply-add is 2 FLOPs; only what
the algorithm needs; three forward passes and one backward of two products a
product, five forward-equivalents a step; nothing made again in the backward
pass is counted.

- attention (scopes ``torso.attn_window`` / ``torso.attn_full``, apart): the
  FIVE projections of a gated layer (``q`` and the gate ``D x H d`` each, ``k``
  and ``v`` ``D x Hkv d`` each, ``o`` ``H d x D``), and ``q k^T`` and ``p v``
  over the query-key pairs the mask keeps: under the window key ``s`` for
  query ``t`` iff ``t - window < s <= t`` (``min(t + 1, window)`` keys for the
  ``t``-th query, 0-based), full ``t + 1``. The norms on the heads, the
  rotation and the gate's sigmoid are not counted (they are not the MXU's).
- experts (scope ``torso.experts``): the three matrices of an expert for every
  assignment a held expert got, as the chunk's ``route_counts`` counted them
  in the critic-loss pass, over the layers that have experts.
- the dense feed-forward (``torso.mlp``: three plain products of
  ``intermediate_size``), the shared expert (``torso.shared_expert``: three
  matrices at its own width, every token) and the router (``torso.route``: ``D
  x experts``) have time metrics only; their products are part of
  ``step_flops``.
"""

from __future__ import annotations

from benchmark.shapes_hybrid import (  # noqa: F401 - shared with cell 6
    expert_counts,
    expert_layers,
    swapped_share,
)
from benchmark.shapes_torso import (  # noqa: F401 - shared with cell 4
    BF16,
    PASSES,
    held_assignments,
    kept_pairs,
    load_max_over_mean,
)

F32 = 4
KINDS = ("sliding_attention", "full_attention")


def layers(t: dict, kind: str) -> int:
    return sum(lt == kind for lt in t["layer_types"])


def attention_counts(t: dict, batch: int, kind: str) -> dict:
    """``{"flops", "bytes"}`` a step for the attention of the layers of
    ``kind`` (one of ``KINDS``)."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    heads, dh = int(t["num_attention_heads"]), int(t["head_dim"])
    hq, hkv = heads * dh, int(t["num_key_value_heads"]) * dh
    window = int(t["sliding_window"]) if kind == "sliding_attention" else None
    matrices = 3 * d * hq + 2 * d * hkv  # q, gate, o and k, v
    pairs = kept_pairs(t_len, window) * heads * dh * 2  # q k^T and p v
    flops = 2.0 * batch * (t_len * matrices + pairs)
    # the bfloat16 matrices once a pass; the float32 residual stream in and
    # out, and q, the gate, k, v and the kernel's output once a sequence
    bytes_ = BF16 * matrices \
        + batch * t_len * (2 * F32 * d + BF16 * (3 * hq + 2 * hkv))
    n = layers(t, kind)
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def dense_flops(t: dict, batch: int) -> float:
    """FLOPs a step of the leading dense layers' SwiGLU."""
    d, wide = int(t["hidden_size"]), int(t["intermediate_size"])
    return PASSES * int(t["num_dense_layers"]) * 2.0 * batch \
        * int(t["tokens"]) * 3 * d * wide


def alike_flops(t: dict, batch: int) -> float:
    """FLOPs a step of what every token goes through in an expert layer: the
    shared expert's three matrices and the router."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    wide = 3 * d * int(t["shared_expert_intermediate_size"]) \
        + d * int(t["num_experts"])
    return PASSES * expert_layers(t) * 2.0 * batch * t_len * wide


def step_flops(t: dict, batch: int, route_counts) -> float:
    """The model FLOPs one gradient step needs: every product above. The
    heads, the norms, the rotation and the optimizer are not counted, so a
    share of the peak reads a little low, never high."""
    rows = held_assignments(t, route_counts)
    return (sum(attention_counts(t, batch, kind)["flops"] for kind in KINDS)
            + dense_flops(t, batch) + alike_flops(t, batch)
            + expert_counts(t, rows)["flops"])
