"""The shapes functions against a hand count, and the plain reference
against the program's fused chunk at a tiny size on the CPU: in float32 the
two must agree to rounding; in the configuration's bfloat16 the program must
stay inside the limits that the control (the reference with fp8 matmul
inputs in the program's place) breaks."""

import copy
import sys
import time

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes
from benchmark.learner import LearnerCell, RunEnv, judge

MANIFEST = manifest.load()


def test_mlp_step_flops_by_hand():
    counts = shapes.step_counts(cellbuild.load_config("humanoid-mlp", False))
    actor = 376 * 256 + 256 * 256 + 256 * 256 + 256 * 17
    critic = 376 * 256 + (256 + 17) * 256 + 256 * 256 + 256 * 51
    assert (actor, critic) == (231_680, 244_736)
    # forward: target actor, target critic, critic, then actor and critic in
    # the actor loss. Backward of the critic loss: every weight gradient,
    # input gradients above the first layer. Backward of the actor loss: the
    # critic's input gradients from head to action, the actor's weight
    # gradients and its input gradients above its first layer.
    fwd = 2 * actor + 3 * critic
    bwd_c = critic + (critic - 376 * 256)
    bwd_a = (critic - 376 * 256) + actor + (actor - 376 * 256)
    macs = fwd + bwd_c + bwd_a + 51 * 51
    assert counts["flops"] == 2 * 256 * macs
    assert counts["flops"] == pytest.approx(1.0798e9, rel=1e-4)
    # 1.07e9 is also what XLA's cost_analysis of make_update printed (PERF.md,
    # PR 21): the compiler skips the same unneeded products. "Backward is
    # twice forward" for every pass gives the 1.35 GFLOP shorthand.
    assert counts["flops_shorthand"] == pytest.approx(1.353e9, rel=1e-3)
    # bytes: parameters, two Adam moments and targets of both networks read
    # and written once in float32, plus the batch's rows
    params = actor + 256 * 3 + 17 + critic + 256 * 3 + 51
    assert counts["params"] == params
    assert counts["row_bytes"] == 2 * 376 * 4 + 17 * 4 + 3 * 4
    assert counts["bytes"] == 4 * 2 * 4 * params + 256 * counts["row_bytes"]
    least, bound = shapes.roofline_seconds(
        counts, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "bytes" and least == pytest.approx(19.64e-6, rel=1e-3)


def test_pixel_step_flops_by_hand():
    cfg = cellbuild.load_config("dmc-pixels-drq", False)
    counts = shapes.step_counts(cfg)
    conv1 = 42 * 42 * 32 * 9 * 9
    conv = 42 * 42 * 32 * 9 * 32
    proj = 42 * 42 * 32 * 50
    enc = conv1 + 3 * conv + proj
    assert enc == 56_165_760
    actor = 50 * 1024 + 1024 * 1024 + 1024 * 6
    critic = 50 * 1024 + (1024 + 6) * 1024 + 1024 * 51
    fwd = 2 * (enc + actor) + 3 * (enc + critic)
    bwd_c = 2 * (enc + critic) - conv1
    # shared encoder: the latent is detached in the actor loss
    bwd_a = (critic - 50 * 1024) + 2 * actor - 50 * 1024
    assert counts["flops"] == 2 * 512 * (fwd + bwd_c + bwd_a + 51 * 51)
    assert counts["flops"] == pytest.approx(4.09e11, rel=5e-3)
    assert counts["row_bytes"] == 2 * 84 * 84 * 9 + 4 * 9
    _least, bound = shapes.roofline_seconds(
        counts, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "flops"


def _cell(workload, dtype, seed=7):
    w = manifest.cell(MANIFEST, workload)
    cfg = copy.deepcopy(cellbuild.load_config(w["config"], True))
    cfg["model"]["compute_dtype"] = dtype
    env = RunEnv(cell=w, cfg=cfg,
                 traffic=cellbuild.load_traffic("learn-static", True),
                 seed=seed, seconds=0.0, trace=False, rehearsal=True,
                 fault="", t_start=time.perf_counter(), trace_dir="", wanted=frozenset(),
                 compile_seconds=lambda: 0.0,
                 log=lambda m: print(m, file=sys.stderr))
    cell = LearnerCell(env)
    cell.first_chunk()
    cell.release()
    return cell


STATIC = [w["name"] for w in MANIFEST["workloads"]
          if w["traffic"] == "learn-static"]


@pytest.mark.parametrize("workload", STATIC)
def test_reference_agrees_with_the_fused_chunk_in_float32(workload):
    """Same arithmetic, another order of operations: float32 rounding only.
    Losses and TD errors agree to 1e-5; the Adam moments and the parameter
    change by the worst leaf to 2e-3 (Adam divides by sqrt(v), which turns a
    1e-7 difference in a near-zero gradient into a visible one). The pixel
    cell passes only if the reference derives the same shift offsets from
    the learner's key as the program does."""
    got = _cell(workload, "float32").check_first_chunk()
    assert got["critic_loss_gap"] < 1e-5
    assert got["actor_loss_gap"] < 1e-5
    assert got["td_gap"] < 1e-5
    assert got["moment_gap"] < 2e-3
    assert got["update_gap"] < 2e-3
    assert got["idx_out_of_range"] == 0
    assert got["leaf_gap"] < 1e-5 and got["root_gap"] < 1e-5


@pytest.mark.parametrize("workload", STATIC)
def test_the_control_fails_where_the_program_passes(workload):
    """bfloat16 as configured stays inside the rehearsal's limits; the
    reference with fp8 matmul inputs put in the program's place breaks at
    least one of them. (The chip readings at the cells' own sizes are in
    PERF.md; ``benchmark/tools/calibrate.py`` makes them.)"""
    cell = _cell(workload, "bfloat16")
    limits = cell.env.cfg["limits"]
    quiet = lambda _m: None  # noqa: E731
    assert judge(cell.check_first_chunk(), limits, quiet)
    control = cell.control_numbers()
    assert not judge(control, limits, quiet)
    # and by a margin: some number is at least three times its limit
    assert max(control[k] / limits[k] for k in control
               if limits[k] is not None) >= 3.0
