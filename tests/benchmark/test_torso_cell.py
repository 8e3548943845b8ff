"""The torso cell (``humanoid-mellum2-ep4.learn-static``): its configuration
file against the published config and the program's torso block, its driver's
seeding and routing comparison, the operation counts its rooflines use, and
the ``frozen_step`` fault through the real command in rehearsal mode (the
sound rehearsal of every cell, this one included, is
``test_result_line.py``'s)."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes_torso

CELL = "humanoid-mellum2-ep4.learn-static"
CONFIG = cellbuild.load_config("humanoid-mellum2-ep4", False)
TORSO = CONFIG["model"]["torso"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the published widths, written out: the file may not drift from them
PUBLISHED = {
    "hidden_size": 2304, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "sliding_window": 1024, "num_experts": 64,
    "num_experts_per_tok": 8, "moe_intermediate_size": 896,
    "rms_norm_eps": 1e-6, "norm_topk_prob": True, "intermediate_size": 7168,
    "vocab_size": 98304, "max_position_embeddings": 131072,
}


@pytest.mark.parametrize("key, value", sorted(PUBLISHED.items()))
def test_every_width_is_as_published(key, value):
    assert CONFIG[key] == value
    if key in TORSO:
        assert TORSO[key] == value


def test_the_program_reads_one_whole_period_of_the_published_pattern():
    assert CONFIG["layer_types"][:4] == TORSO["layer_types"] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert len(CONFIG["layer_types"]) == 28
    assert CONFIG["num_hidden_layers"] == len(TORSO["layer_types"]) == 4
    assert TORSO["rope_parameters"] == CONFIG["rope_parameters"]
    full = TORSO["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["beta_fast"],
            full["beta_slow"]) == ("yarn", 16, 32, 1)


def test_the_cut_is_written_down_and_keeps_the_floors():
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab", "lm_head"]
    assert CONFIG["published"]["num_hidden_layers"] == 28
    assert CONFIG["published"]["num_experts"] == 64
    assert CONFIG["published"]["vocab_size"] == 98304
    lo, hi = CONFIG["experts_held"]
    assert TORSO["experts_held"] == [lo, hi] and hi - lo == 16 >= 8
    assert CONFIG["vocab"] == TORSO["vocab_rows"] == 98304 // 4
    assert TORSO["vocab_rows"] >= 98304 // 8 and TORSO["bins"] == 1024
    assert CONFIG["lm_head"] is False
    assert CONFIG["model"]["obs_dim"] == TORSO["tokens"] == 4096
    assert "four" in CONFIG["stands_for"] and CONFIG["limits_why"]
    here = CONFIG["parameters_here"]
    d, f = 2304, 896
    layer = 2 * d * 4096 + 2 * d * 512 + d * 64 + 16 * 3 * d * f + 2 * d
    assert here["torso"] == 4 * layer + TORSO["vocab_rows"] * d + d
    assert here["total"] == here["torso"] + here["heads"]
    assert 10.7e9 < 20 * here["total"] < 10.9e9


def test_the_file_holds_every_key_of_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if "Mellum2" in line)
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_seeded_expert_stacks_and_embedding_have_their_own_fan_in():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import learner_static_torso as driver

    cfg = cellbuild.load_config("humanoid-mellum2-ep4", True)
    config = cellbuild.learner_config(cfg)
    _actor, critic = jax.jit(lambda s: driver.seeded_params(config, s))(
        jnp.uint32(12345))
    torso = critic["params"]["torso"]
    std = lambda x: float(jnp.std(x))  # noqa: E731
    assert std(torso["embed"]["kernel"]) == pytest.approx(1.0, rel=0.05)
    layer = torso["layer_0"]
    assert std(layer["gate"]["kernel"]) == pytest.approx(
        1 / math.sqrt(64), rel=0.05)
    assert std(layer["down"]["kernel"]) == pytest.approx(
        1 / math.sqrt(32), rel=0.05)
    assert std(layer["q"]["kernel"]) == pytest.approx(1 / 8, rel=0.05)
    assert float(jnp.min(layer["attn_norm"]["scale"])) == 1.0
    # the heads are datagen.weights' own
    assert std(critic["params"]["critic"]["torso"]["fc1"]["kernel"]) \
        == pytest.approx(1 / 8, rel=0.1)


def test_route_hist_gap_is_a_share_of_a_layers_assignments():
    from benchmark.drivers.learner_static_torso import route_hist_gap

    ref = np.full((4, 64), 2048)
    assert route_hist_gap(ref, ref) == 0.0
    prog = ref.copy()
    prog[2, 5] += 3  # three tokens of layer 2 chose expert 5 over expert 9
    prog[2, 9] -= 3
    assert route_hist_gap(prog, ref) == pytest.approx(6 / 131072)


def test_attention_counts_only_the_pairs_the_mask_keeps():
    t = np.arange(4096)
    dense = (t[None] <= t[:, None]) & (t[None] > t[:, None] - 1024)
    assert shapes_torso.kept_pairs(4096, 1024) == int(dense.sum())
    assert shapes_torso.kept_pairs(4096, None) == 4096 * 4097 // 2
    both = shapes_torso.attention_counts(TORSO, 4)
    window = shapes_torso.attention_counts(TORSO, 4, "sliding_attention")
    full = shapes_torso.attention_counts(TORSO, 4, "full_attention")
    assert both["flops"] == pytest.approx(window["flops"] + full["flops"])
    # one full layer, by hand: projections and 8,390,656 kept pairs a
    # sequence, 32 heads of 128, two products, five passes, batch 4
    proj = 4096 * 2 * (2 * 2304 * 4096 + 2 * 2304 * 512)
    pairs = 2 * 2 * 8390656 * 32 * 128
    assert full["flops"] == pytest.approx(5 * 4 * (proj + pairs))


def test_expert_counts_use_the_assignments_the_counter_saw():
    counts = np.zeros((2, 4, 64), np.int64)
    counts[:, :, :16] = 1000  # held
    counts[:, :, 16:] = 7000  # absent: not this chip's work
    rows = shapes_torso.held_assignments(TORSO, counts)
    assert rows == 4 * 16 * 1000
    got = shapes_torso.expert_counts(TORSO, rows)
    assert got["flops"] == pytest.approx(5 * 2 * rows * 3 * 2304 * 896)
    assert shapes_torso.load_max_over_mean(TORSO, counts) == 1.0
    counts[0, 0, 3] = 2000
    assert shapes_torso.load_max_over_mean(TORSO, counts) > 1.0


def test_a_step_handed_back_unchanged_is_refused():
    # as test_result_line.rehearse: one device, one compute thread
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false",
               PYTHONPATH=manifest.REPO, BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "2", "--trace", "0", "--rehearsal", "1",
         "--fault", "frozen_step"], cwd=manifest.REPO, env=env,
        capture_output=True, text=True, timeout=170)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "correct=false" in proc.stderr, proc.stderr[-3000:]
    assert "update_gap" in proc.stderr and "EXCEEDED" in proc.stderr


def test_the_cell_is_one_chip_and_lists_its_eight_layer_metrics():
    man = manifest.load()
    assert manifest.cell(man, CELL)["chips"] == 1
    traced = manifest.metrics_for(man, CELL, True)
    assert set(traced) == {
        "compile_s", "torso_chunk_device_ms", "attn_window_us_per_step",
        "attn_full_us_per_step", "experts_us_per_step", "route_us_per_step",
        "attn_roofline", "experts_roofline", "expert_load_max_over_mean"}
    assert set(manifest.metrics_for(man, CELL, False)) == {
        "grad_steps_per_s", "setup_s"}


HLO = """HloModule jit_fn, is_scheduled=true

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %kernel.1 = f32[8] custom-call(%p), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512}"
}}, metadata={op_name="jit(fn)/while/body/torso.attn_full/pallas_call"}
  %ragged-dot-none.3 = f32[8] custom-call(%kernel.1), custom_call_target="tpu_custom_call"
  %after.2 = f32[8] add(%kernel.1, %ragged-dot-none.3), metadata={op_name="jit(fn)/while/body/torso.route/add"}
  ROOT %t = (s32[], f32[8]) tuple(%p, %after.2)
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %while.1 = (s32[], f32[8]) while(%a), condition=%body.1, body=%body.1, metadata={op_name="jit(fn)/while"}
  ROOT %r = f32[8] get-tuple-element(%while.1), index=1
}
"""


def test_a_kernel_whose_attributes_span_lines_does_not_hide_what_follows():
    from benchmark import program_trace, torso_trace

    # as the text comes, the parser stops reading the computation at the
    # kernel's continuation lines
    assert "after.2" not in program_trace.parse_program(HLO)
    program = torso_trace.under_experts(program_trace.parse_program(
        torso_trace.one_line_each(HLO)))
    scope = lambda name: program_trace.innermost(  # noqa: E731
        program[name].op_name, torso_trace.ALL_SCOPES)
    assert scope("kernel.1") == "torso.attn_full"
    assert scope("after.2") == "torso.route" and program["after.2"].in_loop
    # XLA's grouped product carries no op_name of its own
    assert scope("ragged-dot-none.3") == "torso.experts"
