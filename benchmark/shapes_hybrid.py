"""Operations and bytes the LFM2 torso's layers need in one gradient step,
from the configuration's sizes and the routing the program counted. They
feed ``conv_roofline``, ``hybrid_attn_roofline`` and
``hybrid_experts_roofline`` and live with the benchmark so that no later PR
can move them.

Counting rule (``shapes_torso.py``'s): a multiply-add is 2 FLOPs; only what
the algorithm needs; three forward passes and one backward of two products
a product, five forward-equivalents a step; nothing made again in the
backward pass is counted.

- short convolution (scope ``torso.conv``): the FLOPs are the two
  projections' (``in_proj`` ``D x 3 D``, ``out_proj`` ``D x D``); the bytes
  are the gates' and taps' alone, ``b``, ``c``, ``u`` read and ``c * s``
  written once in the compute dtype. The taps' 2 L and the gates' 2
  multiply-adds a channel are not counted as FLOPs (they are not the MXU's)
  and the projections' matrices and the residual stream not as bytes: the
  larger of the two bounds is the share's numerator, so the share says how
  near the operator runs to whichever of the MXU and the memory it would be
  bound by if the other were free.
- attention (scope ``torso.attn_full``): the four projections at this
  model's widths (32 query and 8 key/value heads of 64), and ``q k^T`` and
  ``p v`` over the causal pairs at 64-wide heads: a form that pads the
  heads to 128 is given no credit for the padding.
- experts (scope ``torso.experts``): the three matrices of an expert for
  every assignment a held expert got, as the chunk's ``route_counts``
  counted them in the critic-loss pass, over the layers that have experts.
- the dense feed-forward (``torso.mlp``) is three plain products and has a
  time metric only.
"""

from __future__ import annotations

import numpy as np

from benchmark.shapes_torso import (  # noqa: F401 - shared with cell 4
    BF16,
    PASSES,
    held_assignments,
    load_max_over_mean,
)


def causal_pairs(t_len: int) -> int:
    return t_len * (t_len + 1) // 2


def _layers(t: dict, kind: str) -> int:
    return sum(lt == kind for lt in t["layer_types"])


def expert_layers(t: dict) -> int:
    return len(t["layer_types"]) - int(t.get("num_dense_layers", 0))


def conv_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the short-convolution operators of
    all ``conv`` layers."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    flops = 2.0 * batch * t_len * (d * 3 * d + d * d)
    bytes_ = BF16 * batch * t_len * (3 * d + d)
    n = _layers(t, "conv")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def attention_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the ``full_attention`` layers."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    heads, dh = int(t["num_attention_heads"]), int(t["head_dim"])
    hq, hkv = heads * dh, int(t["num_key_value_heads"]) * dh
    proj = t_len * (2 * d * hq + 2 * d * hkv)  # q, o and k, v
    pairs = causal_pairs(t_len) * heads * dh * 2  # q k^T and p v
    flops = 2.0 * batch * (proj + pairs)
    # as shapes_torso: the bfloat16 matrices once a pass; the float32
    # residual stream in and out and q, k, v, the output once a sequence
    bytes_ = BF16 * (2 * d * hq + 2 * d * hkv) \
        + batch * t_len * (2 * 4 * d + BF16 * 2 * (hq + hkv))
    n = _layers(t, "full_attention")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def expert_counts(t: dict, assignments: float) -> dict:
    """``{"flops", "bytes"}`` a step for ``assignments`` rows (all expert
    layers together) through an expert's three matrices."""
    d, f = int(t["hidden_size"]), int(t["moe_intermediate_size"])
    lo, hi = t["experts_held"]
    flops = 2.0 * assignments * 3 * d * f
    # each held expert's bfloat16 matrices once a layer and a pass; a row in
    # (d), its two intermediates (f each) and out (d)
    bytes_ = BF16 * (expert_layers(t) * (hi - lo) * 3 * d * f
                     + assignments * (2 * d + 3 * f))
    return {"flops": PASSES * flops, "bytes": PASSES * bytes_}


def swapped_share(t: dict, bias_swapped, batch: int) -> float:
    """Percent of a layer's assignments that the routing bias changed: the
    mean over steps and layers of ``bias_swapped [K, expert layers]``."""
    every = batch * int(t["tokens"]) * int(t["num_experts_per_tok"])
    return float(100.0 * np.mean(np.asarray(bias_swapped, np.float64))
                 / every)
