"""Operations and bytes the torso's two kernels' layers need in one gradient
step, from the configuration's sizes and the routing the program counted.
They feed ``attn_roofline`` and ``experts_roofline`` and live with the
benchmark so that no later PR can move them.

Counting rule (as ``shapes.py``): a multiply-add is 2 FLOPs; only what the
algorithm needs. A step runs the torso three times forward (target on
``next_obs``; under the critic loss; the stepped torso for the actor loss)
and once backward, and a backward pass is two products for each product of
the forward pass (input gradient and weight gradient; in attention the
gradients of both operands of each product): five forward-equivalents a
step. The forward pass made again in the backward pass (rematerialisation)
is not counted, nor is the score product the attention backward makes
again.

- attention (scopes ``torso.attn_window`` / ``torso.attn_full``): the four
  projections, and ``q k^T`` and ``p v`` over the query-key pairs the
  causal / window mask keeps, nothing else.
- experts (scope ``torso.experts``): the three matrices of an expert for
  every assignment a held expert got, as the chunk's ``route_counts``
  counted them in the critic-loss pass (the other two passes route the same
  tokens through parameters a step apart and are taken as equal), not
  ``tokens x k / shares``.
"""

from __future__ import annotations

import numpy as np

PASSES = 5.0  # three forward, one backward of two products a product
BF16 = 2


def kept_pairs(t_len: int, window: int | None) -> int:
    """Query-key pairs a causal (and windowed) mask keeps."""
    t = np.arange(1, t_len + 1, dtype=np.int64)
    return int(np.sum(t if window is None else np.minimum(t, window)))


def attention_counts(t: dict, batch: int, kind: str | None = None) -> dict:
    """``{"flops", "bytes"}`` a step for the attention layers of ``kind``
    (``"sliding_attention"``, ``"full_attention"`` or both)."""
    d = int(t["hidden_size"])
    hq = int(t["num_attention_heads"]) * int(t["head_dim"])
    hkv = int(t["num_key_value_heads"]) * int(t["head_dim"])
    t_len = int(t["tokens"])
    flops = bytes_ = 0.0
    for lt in t["layer_types"]:
        if kind is not None and lt != kind:
            continue
        window = int(t["sliding_window"]) if lt == "sliding_attention" \
            else None
        proj = t_len * (2 * d * hq + 2 * d * hkv)  # q, o and k, v
        pairs = kept_pairs(t_len, window) * int(t["num_attention_heads"]) \
            * int(t["head_dim"]) * 2  # q k^T and p v
        flops += 2.0 * batch * (proj + pairs)
        # the bfloat16 matrices once a pass; the float32 residual stream
        # in and out and q, k, v, the kernel's output once a sequence
        bytes_ += BF16 * (2 * d * hq + 2 * d * hkv) \
            + batch * t_len * (2 * 4 * d + BF16 * 2 * (hq + hkv))
    return {"flops": PASSES * flops, "bytes": PASSES * bytes_}


def held_assignments(t: dict, route_counts) -> float:
    """Assignments a step gave the experts held here, summed over layers:
    the mean over the steps of ``route_counts [K, layers, experts]``."""
    lo, hi = t["experts_held"]
    counts = np.asarray(route_counts, np.float64)
    return float(np.mean(np.sum(counts[..., lo:hi], axis=(-1, -2))))


def expert_counts(t: dict, assignments: float) -> dict:
    """``{"flops", "bytes"}`` a step for ``assignments`` rows through an
    expert's three matrices."""
    d, f = int(t["hidden_size"]), int(t["moe_intermediate_size"])
    lo, hi = t["experts_held"]
    flops = 2.0 * assignments * 3 * d * f
    # each held expert's bfloat16 matrices once a layer and a pass; a row in
    # (d), its two intermediates (f each) and out (d)
    bytes_ = BF16 * (len(t["layer_types"]) * (hi - lo) * 3 * d * f
                     + assignments * (2 * d + 3 * f))
    return {"flops": PASSES * flops, "bytes": PASSES * bytes_}


def load_max_over_mean(t: dict, route_counts) -> float:
    """The busiest held expert's assignments over the held experts' mean,
    a layer and a step at a time, averaged."""
    lo, hi = t["experts_held"]
    held = np.asarray(route_counts, np.float64)[..., lo:hi]
    return float(np.mean(np.max(held, axis=-1)
                         / np.maximum(np.mean(held, axis=-1), 1e-30)))
