"""Operations and bytes the looped Ouro torso needs in one gradient step, from
the configuration's sizes alone, and the two shares read off its exit
distribution. They feed ``loop_attn_roofline``, ``loop_mlp_roofline``,
``loop_step_mfu``, ``exit_last_share`` and ``exit_entropy_share`` and live
with the benchmark so that no later PR can move them.

Counting rule (``shapes_torso.py``'s): a multiply-add is 2 FLOPs; only what
the algorithm needs; three forward passes and one backward of two products a
product, five forward-equivalents a step; nothing made again in the backward
pass is counted. A looped torso applies each of its ``L`` layers
``total_ut_steps`` times a torso pass: ``R x L`` layer applications, every
one of them needed.

- attention (scope ``torso.attn_full``): the four projections (16 query
  heads on 16 key/value heads of 128: all four are ``D x D``), and ``q k^T``
  and ``p v`` over the causal pairs.
- the SwiGLU (scope ``torso.mlp``): its three products.
- the whole step (``loop_step_mfu``): those two, which are all of the
  torso's products; the heads, the gate, the norms and the optimizer are not
  counted, so the share reads a little low, never high.
"""

from __future__ import annotations

import numpy as np

from benchmark.shapes_hybrid import causal_pairs  # noqa: F401 - shared
from benchmark.shapes_torso import BF16, PASSES


def applications(t: dict) -> int:
    """Layer applications a torso pass: every layer, every pass."""
    return int(t["total_ut_steps"]) * len(t["layer_types"])


def attention_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the attention operators."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    heads, dh = int(t["num_attention_heads"]), int(t["head_dim"])
    hq, hkv = heads * dh, int(t["num_key_value_heads"]) * dh
    proj = t_len * (2 * d * hq + 2 * d * hkv)  # q, o and k, v
    pairs = causal_pairs(t_len) * heads * dh * 2  # q k^T and p v
    flops = 2.0 * batch * (proj + pairs)
    # as shapes_torso: the bfloat16 matrices once an application; the
    # float32 residual stream in and out and q, k, v, the output a sequence
    bytes_ = BF16 * (2 * d * hq + 2 * d * hkv) \
        + batch * t_len * (2 * 4 * d + BF16 * 2 * (hq + hkv))
    n = applications(t)
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def mlp_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the dense SwiGLU feed-forwards."""
    d, f = int(t["hidden_size"]), int(t["intermediate_size"])
    t_len = int(t["tokens"])
    flops = 2.0 * batch * t_len * 3 * d * f
    # the three bfloat16 matrices once an application; the float32 residual
    # stream in and out, the two gates and their product once a sequence
    bytes_ = BF16 * 3 * d * f + batch * t_len * (2 * 4 * d + BF16 * 3 * f)
    n = applications(t)
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def step_flops(t: dict, batch: int) -> float:
    """The model FLOPs one gradient step needs (module docstring)."""
    return attention_counts(t, batch)["flops"] + mlp_counts(t, batch)["flops"]


def exit_last_share(exit_dist) -> float:
    """Percent of the exit distribution on the last pass: the mean over the
    steps of ``exit_dist [K, R]``'s last column. 100 is a gate that never
    exits early, 0 one that never reaches the last pass."""
    return float(100.0 * np.mean(np.asarray(exit_dist, np.float64)[..., -1]))


def exit_entropy_share(exit_dist) -> float:
    """Percent of ``ln R`` that the entropy of the mean exit distribution
    reaches (the mean over the steps of ``exit_dist [K, R]``): 100 is
    uniform over the passes, 0 a gate that always leaves at one pass."""
    dist = np.asarray(exit_dist, np.float64)
    p = np.mean(dist.reshape(-1, dist.shape[-1]), axis=0)
    p = p[p > 0]
    return float(100.0 * -np.sum(p * np.log(p)) / np.log(dist.shape[-1]))
