"""Operations and bytes the Nemotron-H torso's blocks need in one gradient
step, from the configuration's sizes and the routing the program counted.
They feed ``mamba_roofline``, ``ssd_scan_roofline``, ``ssm_attn_roofline``,
``ssm_experts_roofline`` and ``ssm_step_mfu`` and live with the benchmark so
that no later PR can move them.

Counting rule (``shapes_torso.py``'s): a multiply-add is 2 FLOPs; only what
the algorithm needs; three forward passes and one backward of two products a
product, five forward-equivalents a step; nothing made again in the backward
pass is counted. A block is one branch, so each kind is counted over the
blocks of its character in ``hybrid_override_pattern``.

- Mamba-2 mixer without its recurrence (scope ``torso.mamba``): the FLOPs are
  the two projections' (``in_proj`` ``D x (2 inner + 2 G N + H)``,
  ``out_proj`` ``inner x D``); the bytes are ``z``, ``xBC`` and ``y``, each
  written once and read once in the compute dtype. The taps, the SiLU and the
  gated norm are not counted as FLOPs (they are not the MXU's); the larger of
  the two bounds is the share's numerator.
- the recurrence (scope ``torso.ssd_scan``): **as the model writes it**,
  token by token: decay the ``[P, N]`` state of a head (``P N`` multiplies),
  write ``dt x b^T`` into it and read it with ``c`` (``2 P N`` each): ``5 P N
  H`` FLOP a token; the bytes are ``xs`` and ``y`` (``H P`` each), ``B`` and
  ``C`` (``G N`` each) and ``dt`` (``H``) in float32, read or written once.
  The count knows neither the chunk nor the form: the chunked form's
  decay-masked products inside a chunk are its own way of doing these, and a
  later kernel is read by the same yardstick.
- attention (scope ``torso.attn_full``): the four projections at this model's
  widths (32 query heads on 2 key/value heads of 128) and ``q k^T`` and ``p
  v`` over the causal pairs.
- experts (scope ``torso.experts``): the TWO matrices of a relu2 expert for
  every assignment a held expert got, as the chunk's ``route_counts`` counted
  them in the critic-loss pass.
- the shared expert (``torso.shared_expert``: two matrices at its own width,
  every token) and the router (``torso.route``: ``D x experts``) have time
  metrics only; their products are part of ``step_flops``.
"""

from __future__ import annotations

import numpy as np

from benchmark.shapes_hybrid import causal_pairs  # noqa: F401 - shared
from benchmark.shapes_torso import (  # noqa: F401 - shared with cell 4
    BF16,
    PASSES,
    held_assignments,
    load_max_over_mean,
)

F32 = 4


def blocks(t: dict, kind: str) -> int:
    """How many blocks of the character ``kind`` (``M``, ``E``, ``*``)."""
    return t["hybrid_override_pattern"].count(kind)


def mamba_widths(t: dict) -> tuple:
    """``(heads, head width, inner, B or C's width)``."""
    h, p = int(t["mamba_num_heads"]), int(t["mamba_head_dim"])
    return h, p, h * p, int(t["n_groups"]) * int(t["ssm_state_size"])


def mamba_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the ``M`` blocks without their
    recurrence."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    h, _p, inner, bc = mamba_widths(t)
    flops = 2.0 * batch * t_len * (d * (2 * inner + 2 * bc + h) + inner * d)
    bytes_ = BF16 * batch * t_len * 2 * (3 * inner + 2 * bc)
    n = blocks(t, "M")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def ssd_scan_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the recurrence as the model writes
    it."""
    t_len, n_state = int(t["tokens"]), int(t["ssm_state_size"])
    h, p, inner, bc = mamba_widths(t)
    flops = 5.0 * p * n_state * h * batch * t_len
    bytes_ = F32 * batch * t_len * (2 * inner + 2 * bc + h)
    n = blocks(t, "M")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def attention_counts(t: dict, batch: int) -> dict:
    """``{"flops", "bytes"}`` a step for the ``*`` blocks."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    heads, dh = int(t["num_attention_heads"]), int(t["head_dim"])
    hq, hkv = heads * dh, int(t["num_key_value_heads"]) * dh
    proj = t_len * (2 * d * hq + 2 * d * hkv)  # q, o and k, v
    pairs = causal_pairs(t_len) * heads * dh * 2  # q k^T and p v
    flops = 2.0 * batch * (proj + pairs)
    # as shapes_torso: the bfloat16 matrices once a pass; the float32
    # residual stream in and out and q, k, v, the output once a sequence
    bytes_ = BF16 * (2 * d * hq + 2 * d * hkv) \
        + batch * t_len * (2 * F32 * d + BF16 * 2 * (hq + hkv))
    n = blocks(t, "*")
    return {"flops": PASSES * n * flops, "bytes": PASSES * n * bytes_}


def expert_counts(t: dict, assignments: float) -> dict:
    """``{"flops", "bytes"}`` a step for ``assignments`` rows (all ``E``
    blocks together) through a relu2 expert's two matrices."""
    d, f = int(t["hidden_size"]), int(t["moe_intermediate_size"])
    lo, hi = t["experts_held"]
    flops = 2.0 * assignments * 2 * d * f
    # each held expert's bfloat16 matrices once a block and a pass; a row in
    # (d), its hidden activation written and read (f each) and out (d)
    bytes_ = BF16 * (blocks(t, "E") * (hi - lo) * 2 * d * f
                     + assignments * (2 * d + 2 * f))
    return {"flops": PASSES * flops, "bytes": PASSES * bytes_}


def alike_flops(t: dict, batch: int) -> float:
    """FLOPs a step of what every token goes through in an ``E`` block: the
    shared expert's two matrices and the router."""
    d, t_len = int(t["hidden_size"]), int(t["tokens"])
    wide = 2 * d * int(t["shared_expert_intermediate_size"]) \
        + d * int(t["num_experts"])
    return PASSES * blocks(t, "E") * 2.0 * batch * t_len * wide


def step_flops(t: dict, batch: int, route_counts) -> float:
    """The model FLOPs one gradient step needs: every product above. The
    heads, the norms, the taps and the optimizer are not counted, so a share
    of the peak reads a little low, never high."""
    rows = held_assignments(t, route_counts)
    return (mamba_counts(t, batch)["flops"]
            + ssd_scan_counts(t, batch)["flops"]
            + attention_counts(t, batch)["flops"]
            + expert_counts(t, rows)["flops"] + alike_flops(t, batch))


def kept_share(ssd_kept) -> float:
    """Percent: the mean over steps and blocks of ``ssd_kept [K, M blocks]``,
    the mean of ``exp(dt A)``: 100 never forgets, 0 has no memory."""
    return float(100.0 * np.mean(np.asarray(ssd_kept, np.float64)))


def swapped_share(t: dict, bias_swapped, batch: int) -> float:
    """Percent of a block's assignments that the routing bias changed: the
    mean over steps and blocks of ``bias_swapped [K, E blocks]``."""
    every = batch * int(t["tokens"]) * int(t["num_experts_per_tok"])
    return float(100.0 * np.mean(np.asarray(bias_swapped, np.float64))
                 / every)
