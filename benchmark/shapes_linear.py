"""Operations and bytes the Qwen3-Next torso's layers need in one gradient
step, from the configuration's sizes and the routing the program counted.
They feed ``deltanet_roofline``, ``delta_scan_roofline``,
``gated_attn_roofline`` and ``linear_experts_roofline`` and live with the
benchmark so that no later PR can move them.

Counting rule (``shapes_torso.py``'s): a multiply-add is 2 FLOPs; only what
the algorithm needs; three forward passes and one backward of two products
a product, five forward-equivalents a step; nothing made again in the
backward pass is counted.

- Gated DeltaNet operator without its recurrence (scope ``torso.deltanet``):
  the FLOPs are the three projections' (``in_proj_qkvz`` ``D x (2 Wk + 2
  Wv)``, ``in_proj_ba`` ``D x 2 Hv``, ``out_proj`` ``Wv x D``); the bytes are
  ``[q, k, v, z]`` written by the projection and read by what follows, once
  each in the compute dtype. The taps, the SiLU, the output norm and gate
  are not counted as FLOPs (they are not the MXU's); the larger of the two
  bounds is the share's numerator.
- the recurrence (scope ``torso.delta_scan``): **as the model writes it**,
  token by token: decay the state, read it with ``k``, write ``k d^T`` into
  it, read it with ``q``: three ``[Dk, Dv]`` products a value head and token,
  ``6 Dk Dv Hv`` FLOP a token; the bytes are ``q``, ``k`` (key heads), ``v``,
  ``o`` (value heads) in float32 and ``g``, ``beta``, read or written once.
  The count knows neither the chunk nor the form: the chunked form's solve
  and its products inside a chunk are its own way of doing these, and a
  later kernel is read by the same yardstick.
- gated attention (scope ``torso.attn_full``): the projections at this
  model's widths (``q`` twice as wide for the gate, 16 query and 2 key/value
  heads of 256) and ``q k^T`` and ``p v`` over the causal pairs.
- experts (scope ``torso.experts``): the three matrices of an expert for
  every assignment a held expert got, as the chunk's ``route_counts``
  counted them in the critic-loss pass.
- the shared expert (``torso.shared_expert``) and the routing
  (``torso.route``) have time metrics only.
"""

from __future__ import annotations

import numpy as np

from benchmark.shapes_hybrid import causal_pairs  # noqa: F401
from benchmark.shapes_torso import (  # noqa: F401 - shared with cell 4
    BF16,
    PASSES,
    expert_counts,
    held_assignments,
    load_max_over_mean,
)

F32 = 4


def _layers(t: dict, kind: str) -> int:
    return sum(lt == kind for lt in t["layer_types"])


def _linear_widths(t: dict) -> tuple:
    """``(Wk, Wv, Hk, Hv, Dk, Dv)``."""
    hk, hv = int(t["linear_num_key_heads"]), int(t["linear_num_value_heads"])
    dk, dv = int(t["linear_key_head_dim"]), int(t["linear_value_head_dim"])
    return hk * dk, hv * dv, hk, hv, dk, dv


def deltanet_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the ``linear_attention`` operators
    without their recurrence."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    wk, wv, _hk, hv, _dk, _dv = _linear_widths(t)
    flops = 2.0 * batch * t_len * (d * (2 * wk + 2 * wv) + d * 2 * hv
                                   + wv * d)
    bytes_ = BF16 * batch * t_len * 2 * (2 * wk + 2 * wv)
    n = _layers(t, "linear_attention")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def delta_scan_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the recurrence as the model writes
    it."""
    t_len = int(t["tokens"])
    _wk, _wv, hk, hv, dk, dv = _linear_widths(t)
    flops = 6.0 * dk * dv * hv * batch * t_len
    bytes_ = F32 * batch * t_len * (2 * hk * dk + 2 * hv * dv + 2 * hv)
    n = _layers(t, "linear_attention")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def attention_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the gated ``full_attention``
    layers."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    heads, dh = int(t["num_attention_heads"]), int(t["head_dim"])
    hq, hkv = heads * dh, int(t["num_key_value_heads"]) * dh
    wq = 2 * hq if t.get("attn_output_gate") else hq
    proj = t_len * (d * wq + d * hq + 2 * d * hkv)  # q (and gate), o; k, v
    pairs = causal_pairs(t_len) * heads * dh * 2  # q k^T and p v
    flops = 2.0 * batch * (proj + pairs)
    # as shapes_torso: the bfloat16 matrices once a pass; the float32
    # residual stream in and out and q (with its gate), k, v, the output once
    bytes_ = BF16 * (d * wq + d * hq + 2 * d * hkv) \
        + batch * t_len * (2 * F32 * d + BF16 * (wq + hq + 2 * hkv))
    n = _layers(t, "full_attention")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def kept_share(delta_kept) -> float:
    """Percent: the mean over steps and layers of ``delta_kept [K, linear
    layers]``, the mean of ``exp(g)``: 100 never forgets, 0 has no memory."""
    return float(100.0 * np.mean(np.asarray(delta_kept, np.float64)))
