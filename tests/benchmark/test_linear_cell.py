"""The Qwen3-Next torso cell (``humanoid-qwen3next-ep32.learn-static``): its
configuration file against the published config, the catalog and the
program's own parameter tree, its driver's seeded weights and compared
numbers, both controls at rehearsal size, the operation counts its rooflines
use against brute force, and what the manifest lists for it (the sound
rehearsal of every cell, this one included, is ``test_result_line.py``'s; the
files found by name ``test_manifest_files.py``'s)."""

import json
import os

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes_linear

CELL = "humanoid-qwen3next-ep32.learn-static"
CONFIG = cellbuild.load_config("humanoid-qwen3next-ep32", False)
TORSO = CONFIG["model"]["torso"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FIRST_METRIC = "linear_chunk_device_ms"  # the first entry this cell brought
METRICS = [
    "linear_chunk_device_ms", "deltanet_us_per_step",
    "delta_scan_us_per_step", "gated_attn_us_per_step",
    "shared_expert_us_per_step", "linear_route_us_per_step",
    "linear_experts_us_per_step", "deltanet_roofline", "delta_scan_roofline",
    "gated_attn_roofline", "linear_experts_roofline", "delta_kept_share",
    "linear_expert_load_max_over_mean"]

# the published widths, written out: the file may not drift from them
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 2,
    "head_dim": 256, "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "full_attention_interval": 4,
    "num_experts": 512, "num_experts_per_tok": 10,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "intermediate_size": 5120, "rms_norm_eps": 1e-6, "vocab_size": 151936,
    "max_position_embeddings": 262144, "model_type": "qwen3_next",
}


@pytest.mark.parametrize("key, value", sorted(PUBLISHED.items()))
def test_every_width_is_as_published(key, value):
    assert CONFIG[key] == value
    if key in TORSO:
        assert TORSO[key] == value


def test_the_layers_reach_the_program_as_published():
    # one whole period of full_attention_interval 4, published layers 1-4
    assert TORSO["layer_types"] == ["linear_attention"] * 3 + [
        "full_attention"]
    assert len(TORSO["layer_types"]) == CONFIG["full_attention_interval"]
    assert TORSO["name"] == "qwen3next" and TORSO["attn_output_gate"] is True
    assert TORSO["qk_norm"] is True and TORSO["router_scores"] == "softmax"
    assert TORSO["rope_parameters"] == {"full_attention": {
        "rope_type": "default", "rope_theta": CONFIG["rope_theta"]}}
    assert TORSO["tokens"] == CONFIG["model"]["obs_dim"] == 16384
    assert 41 * (376 + 17) == 16113 <= 16384
    assert "num_dense_layers" not in TORSO  # every layer has experts
    assert CONFIG["model"]["compute_dtype"] == "bfloat16"
    assert CONFIG["learner"] == {**CONFIG["learner"], "batch_size": 2, "k": 1}
    # the rehearsal has both kinds of layer, at least three chunks of the
    # scan a sequence and a strict share of the experts
    small = cellbuild.load_config("humanoid-qwen3next-ep32", True)["model"][
        "torso"]
    assert small["layer_types"] == ["linear_attention", "full_attention"]
    assert small["tokens"] >= 3 * 64 and small["tokens"] % 64
    lo, hi = small["experts_held"]
    assert 0 < hi - lo < small["num_experts"]
    assert small["shared_expert_intermediate_size"] > 0
    assert small["partial_rotary_factor"] == 0.25


def test_the_cut_is_written_down_and_keeps_the_floors():
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab", "lm_head"]
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 48 and pub["num_experts"] == 512
    assert pub["vocab_size"] == 151936
    assert CONFIG["num_hidden_layers"] == len(TORSO["layer_types"]) == 4
    lo, hi = CONFIG["experts_held"]
    assert TORSO["experts_held"] == [lo, hi] and hi - lo == 16 >= 8
    assert CONFIG["vocab"] == TORSO["vocab_rows"] == 151936 // 8
    assert TORSO["bins"] == 1024 and CONFIG["lm_head"] is False
    assert "one chip of 32" in CONFIG["stands_for"]
    assert "44 layers left out" in CONFIG["stands_for"]
    assert "32 chips" in CONFIG["reduced_why"]
    for text in (CONFIG["limits_why"], CONFIG["reduced_why"],
                 *CONFIG["assumed"]):
        assert text and "PLACEHOLDER" not in text
    assert "reset64" in CONFIG["limits_why"] and "fp8" in CONFIG["limits_why"]
    for marked in ("contiguous chunks in that order", "scale = 1 + w",
                   "A ~ U(0, 16)", "chunks of 64", "41 Humanoid-v4 steps",
                   "i with i + 32", "the taps' is 4", "640 tokens a pass",
                   "multi-token-prediction"):
        assert any(marked in line for line in CONFIG["assumed"]), marked
    assert CONFIG["seeded_decay"] == {"A": [0.0, 16.0], "dt": [1e-3, 0.1]}


def test_parameters_here_are_the_programs_own_tree():
    import jax

    from d4pg_tpu.learner import init_state

    config = cellbuild.learner_config(CONFIG)
    state = jax.eval_shape(lambda: init_state(config, jax.random.key(0)))
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa
    here = CONFIG["parameters_here"]
    torso = state.critic_params["params"]["torso"]
    assert size(torso) == here["torso"]
    # ISSUE 38's arithmetic, leaf by leaf
    lin, att = torso["layer_0"], torso["layer_3"]
    assert sum(size(lin[n]) for n in (
        "in_proj_qkvz", "in_proj_ba", "conv", "dt_bias", "A_log", "out_norm",
        "out_proj")) == here["deltanet_operator"] \
        == 2048 * 12288 + 2048 * 64 + 8192 * 4 + 32 + 32 + 128 + 4096 * 2048
    assert sum(size(att[n]) for n in ("q", "k", "v", "o", "q_norm",
                                      "k_norm")) \
        == here["attention_operator"] \
        == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    for layer in (lin, att):
        assert sum(size(layer[n]) for n in ("gate", "up", "down")) \
            == here["experts_a_layer"] == 16 * 3 * 2048 * 512
        assert size(layer["router"]) == here["router_a_layer"] == 2048 * 512
        assert sum(size(layer[n]) for n in (
            "shared_gate", "shared_up", "shared_down")) \
            == here["shared_expert_a_layer"] == 3 * 2048 * 512
        assert size(layer["shared_expert_gate"]) \
            == here["shared_expert_gate_a_layer"] == 2048
    for i in (0, 1, 2):
        assert size(torso[f"layer_{i}"]) == here["deltanet_layer"] == 88250560
    assert size(att) == here["attention_layer"] == 81795584
    assert size(torso["embed"]) == here["embedding"] == 18992 * 2048
    assert size(state.critic_params) + size(state.actor_params) \
        == here["total"] == here["torso"] + here["heads"] == 386779012
    assert 7.7e9 < 20 * here["total"] < 7.8e9
    # over the floor of a quarter of the chip's 16.9 GB, under the chip
    assert 0.25 < 20 * here["total"] / 16.9e9 < 0.7
    # the ring the file states: 16,384 rows of two 16,384-wide fields
    row = 4 * (2 * 16384 + 17 + 3)
    assert row == 131152 and 2.1e9 < row * CONFIG["replay"]["capacity"] \
        < 2.2e9


def test_the_file_holds_every_key_of_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if "Qwen3-Next-80B-A3B-Instruct" in line)
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    # no width among the cuts
    assert not [k for k in CONFIG["reduced"] if k.endswith(
        ("_size", "_dim", "_rank", "per_tok"))]


def test_seeded_taps_and_decay_are_put_right_by_the_driver():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import learner_static_linear as driver

    cfg = cellbuild.load_config("humanoid-qwen3next-ep32", True)
    config = cellbuild.learner_config(cfg)
    make = jax.jit(lambda s: driver.seeded_params(cfg, config, s))
    _actor, critic = make(jnp.uint32(12345))
    layers = critic["params"]["torso"]
    std = lambda x: float(jnp.std(x))  # noqa: E731
    lin, att = layers["layer_0"], layers["layer_1"]
    # every leaf at its own fan-in: the taps' is 4, an expert's its rows
    assert std(lin["conv"]["kernel"]) == pytest.approx(0.5, rel=0.2)
    assert std(lin["in_proj_qkvz"]["kernel"]) == pytest.approx(1 / 8, rel=0.1)
    assert std(att["gate"]["kernel"]) == pytest.approx(1 / 8, rel=0.1)
    assert std(att["shared_down"]["kernel"]) == pytest.approx(
        32 ** -0.5, rel=0.1)
    # the decay: A in (0, 16), dt in [1e-3, 1e-1] behind a softplus
    a = np.exp(np.asarray(lin["A_log"]["value"], np.float64))
    dt = np.log1p(np.exp(np.asarray(lin["dt_bias"]["value"], np.float64)))
    assert a.shape == dt.shape == (4,)
    assert np.all((a > 0) & (a < 16)) and len(set(a.tolist())) == 4
    assert np.all((dt > 0.99e-3) & (dt < 0.101)) and len(set(dt.tolist())) == 4
    # another seed, another decay; the same seed, the same
    value = lambda t: np.asarray(t["layer_0"]["A_log"]["value"])  # noqa: E731
    other = make(jnp.uint32(54321))[1]["params"]["torso"]
    again = make(jnp.uint32(12345))[1]["params"]["torso"]
    assert not np.array_equal(value(other), value(layers))
    np.testing.assert_array_equal(value(again), value(layers))
    assert driver.CELL is driver.LinearCell and driver.RESET_EVERY == 64


def test_counter_gap_is_the_largest_relative_difference():
    from benchmark.drivers.learner_static_linear import counter_gap

    ref = np.asarray([[0.8, 0.9, 0.5]])
    assert counter_gap(ref, ref) == 0.0
    assert counter_gap(ref * [1.0, 1.01, 0.98], ref) == pytest.approx(0.02)
    assert counter_gap(ref.astype(np.float32), ref) < 1e-7


def test_deltanet_scan_attention_and_expert_counts_against_brute_force():
    t_len, d, batch = 16384, 2048, 2
    # one DeltaNet operator, one sequence, one forward pass, by hand
    proj = 2 * t_len * (d * 12288 + d * 64 + 4096 * d)
    got = shapes_linear.deltanet_counts(TORSO, batch)
    assert got["flops"] == pytest.approx(5 * 3 * batch * proj)
    # [q, k, v, z] written and read once in bfloat16
    assert got["bytes"] == pytest.approx(
        5 * 3 * batch * 2 * 2 * t_len * 12288)
    # the recurrence token by token: three [128, 128] products a value head
    got = shapes_linear.delta_scan_counts(TORSO, batch)
    assert got["flops"] == pytest.approx(
        5 * 3 * batch * t_len * 6 * 128 * 128 * 32)
    assert got["bytes"] == pytest.approx(
        5 * 3 * batch * t_len * 4 * (2 * 2048 + 2 * 4096 + 2 * 32))
    pairs = shapes_linear.causal_pairs(t_len)
    assert pairs == t_len * (t_len + 1) // 2
    attn = 2 * (t_len * (d * 8192 + 4096 * d + 2 * d * 512)
                + pairs * 16 * 256 * 2)
    got = shapes_linear.attention_counts(TORSO, batch)
    assert got["flops"] == pytest.approx(5 * 1 * batch * attn)
    # brute force at a small size: a loop over every position, head and pair
    small = {**TORSO, "tokens": 40}
    flops = 0
    for _t in range(40):
        for _h in range(32):
            flops += 3 * 2 * 128 * 128  # decay-and-read, write, read
    assert shapes_linear.delta_scan_counts(small, 1)["flops"] \
        == pytest.approx(5 * 3 * flops)
    flops = 0
    for _t in range(40):
        flops += 2 * (d * 12288 + d * 64 + 4096 * d)
    assert shapes_linear.deltanet_counts(small, 1)["flops"] \
        == pytest.approx(5 * 3 * flops)
    flops = 2 * 40 * (d * 8192 + 4096 * d + 2 * d * 512)
    for t in range(40):
        for _s in range(t + 1):
            flops += 2 * 16 * 256 * 2  # one score and one weighted value
    assert shapes_linear.attention_counts(small, 1)["flops"] \
        == pytest.approx(5 * flops)
    # experts: an even load is 640 assignments an expert and a layer
    counts = np.full((1, 4, 512), 640, np.int64)
    rows = shapes_linear.held_assignments(TORSO, counts)
    assert rows == 4 * 16 * 640
    got = shapes_linear.expert_counts(TORSO, rows)
    assert got["flops"] == pytest.approx(5 * 2 * rows * 3 * 2048 * 512)
    assert shapes_linear.load_max_over_mean(TORSO, counts) == 1.0
    assert shapes_linear.kept_share([[0.8, 0.9, 0.7]]) == pytest.approx(80.0)
    # ISSUE 38's ~73 TFLOP a step: these four, the shared expert and router
    shared = 5 * 4 * batch * t_len * 2 * (3 * d * 512 + d)
    router = 5 * 4 * batch * t_len * 2 * d * 512
    total = shapes_linear.deltanet_counts(TORSO, batch)["flops"] \
        + shapes_linear.delta_scan_counts(TORSO, batch)["flops"] \
        + shapes_linear.attention_counts(TORSO, batch)["flops"] \
        + shapes_linear.expert_counts(TORSO, rows)["flops"] + shared + router
    assert 70e12 < total < 76e12


def test_the_cell_is_one_chip_and_lists_its_thirteen_layer_metrics():
    man = manifest.load()
    assert manifest.cell(man, CELL)["chips"] == 1
    traced = manifest.metrics_for(man, CELL, True)
    assert set(traced) == {"compile_s", *METRICS}
    assert set(manifest.metrics_for(man, CELL, False)) == {
        "grad_steps_per_s", "setup_s"}
    # the other torso cells' readers are not asked in this cell, nor this
    # cell's in theirs
    for other in ("humanoid-mellum2-ep4.learn-static",
                  "humanoid-keye2-ep8.learn-static",
                  "humanoid-lfm2-ep4.learn-static"):
        theirs = manifest.metrics_for(man, other, True)
        assert set(theirs) & set(traced) == {"compile_s"}
    for entry in traced.values():
        if entry["name"] != "compile_s":
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "grad_steps_per_s"
            if entry["name"].endswith("_roofline"):
                assert entry["unit"] == "%" and entry["better"] == "higher"
    # the entries this cell brought stand together and in order, from the
    # first of them on: whatever a later PR appends comes behind them
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(FIRST_METRIC)
    assert names[at:at + len(METRICS)] == METRICS
    cells = [w["name"] for w in man["workloads"]]
    assert cells.index(CELL) == cells.index(
        "humanoid-lfm2-ep4.learn-static") + 1
    configs = [c["name"] for c in man["configs"]]
    assert configs.index("humanoid-qwen3next-ep32") == configs.index(
        "humanoid-lfm2-ep4") + 1


def test_the_readers_read_this_cell_and_no_other():
    """On a context that is another cell's (``torso``, ``sparse`` or
    ``hybrid``, not ``linear``) every reader of this cell returns nothing,
    and the other cells' roofline readers return nothing on this cell's."""
    from benchmark import (
        hybrid_trace,
        linear_trace,
        run,
        sparse_trace,
        torso_trace,
    )

    log = lambda _m: None  # noqa: E731
    for key in ("torso", "sparse", "hybrid"):
        theirs = {"log": log, "trace": object(), key: TORSO, "k": 1,
                  "chunk_text": "", "chunk_program": "jit_fn",
                  "torso_trace": None, "sparse_trace": None,
                  "hybrid_trace": None, "batch_size": 2,
                  "route_counts": np.ones((1, 4, 512)),
                  "delta_kept": np.ones((1, 3))}
        for name in METRICS:
            assert run.layer_reader(name)(dict(theirs)) is None, name
    mine = {"log": log, "trace": object(), "linear": TORSO, "k": 1,
            "linear_trace": None, "batch_size": 2}
    assert torso_trace.attn_roofline(dict(mine)) is None
    assert sparse_trace.attention_roofline(dict(mine)) is None
    assert hybrid_trace.conv_roofline(dict(mine)) is None
    assert linear_trace.delta_scan_roofline(dict(mine)) is None  # no trace
    kept = np.asarray([[0.85, 0.86, 0.82]])
    assert linear_trace.kept_share({**mine, "delta_kept": kept}) \
        == pytest.approx(84.333, abs=1e-2)
    counts = np.full((1, 4, 512), 640)
    assert linear_trace.load_max_over_mean(
        {**mine, "route_counts": counts}) == 1.0
    # a program without the counters (the parent's) gives the readers nothing
    assert linear_trace.kept_share({**mine, "delta_kept": None}) is None
    assert linear_trace.load_max_over_mean(
        {**mine, "route_counts": None}) is None


def test_the_scopes_are_read_from_a_chunk_programs_text():
    """``linear_trace.analyse`` on a hand-made trace: each scope's time goes
    to its own metric, a roofline is the least time over the time spent."""
    from benchmark import linear_trace, program_trace

    assert {"torso.deltanet", "torso.delta_scan", "torso.attn_full",
            "torso.shared_expert", "torso.route", "torso.experts"} \
        <= set(linear_trace.LINEAR_SCOPES)
    assert set(program_trace.TOP_SCOPES) <= set(linear_trace.ALL_SCOPES)
    found = {"total": 3.0, "covered": 1.0, "step": {
        s: 0.0 for s in linear_trace.ALL_SCOPES}}
    found["step"].update({"torso.delta_scan": 1.5, "torso.deltanet": 0.5,
                          "torso.attn_full": 0.4})
    ctx = {"log": lambda _m: None, "linear": TORSO, "linear_trace": found,
           "batch_size": 2, "trace": object(),
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert linear_trace.scope_us(ctx, "torso.delta_scan") \
        == pytest.approx(1.5e6)
    assert linear_trace.chunk_ms(ctx) == 3000.0
    # the recurrence is bound by its bytes, the projections by their FLOPs
    scan = shapes_linear.delta_scan_counts(TORSO, 2)
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    assert linear_trace.delta_scan_roofline(ctx) == pytest.approx(
        100 * scan["bytes"] / 819e9 / 1.5)
    flops = shapes_linear.deltanet_counts(TORSO, 2)["flops"]
    assert linear_trace.deltanet_roofline(ctx) == pytest.approx(
        100 * flops / 197e12 / 0.5)
    flops = shapes_linear.attention_counts(TORSO, 2)["flops"]
    assert linear_trace.attention_roofline(ctx) == pytest.approx(
        100 * flops / 197e12 / 0.4)
    # a scope no operation carries reads 0.0, not a division by zero
    assert linear_trace.roofline(ctx, {"flops": 1.0, "bytes": 1.0}, "x",
                                 "torso.experts") == 0.0


@pytest.fixture(scope="module")
def rehearsed():
    """The cell at rehearsal size, its first chunk run and its program
    given up: what ``benchmark/tools/calibrate_controls.py`` does a seed."""
    import time

    from benchmark.drivers import learner_static_linear as driver
    from benchmark.learner import RunEnv

    man = manifest.load()
    cell = manifest.cell(man, CELL)
    env = RunEnv(cell=cell,
                 cfg=cellbuild.load_config(cell["config"], True),
                 traffic=cellbuild.load_traffic(cell["traffic"], True),
                 seed=2147483659, seconds=0.0, trace=False, rehearsal=True,
                 fault="", t_start=time.perf_counter(), trace_dir="",
                 wanted=frozenset(), compile_seconds=lambda: 0.0,
                 log=lambda _m: None)
    lc = driver.CELL(env)
    lc.first_chunk()
    lc.release()
    return lc


def test_both_controls_fail_where_the_program_passes(rehearsed):
    """bfloat16 as configured stays inside the rehearsal's limits; the
    reference with fp8 product inputs, and the reference whose state is set
    to zero at every 64th token, each break at least one of them."""
    from benchmark.learner import judge
    from benchmark.tools.calibrate_controls import exceeded

    limits = rehearsed.env.cfg["limits"]
    quiet = lambda _m: None  # noqa: E731
    sound = rehearsed.check_first_chunk()
    assert {"delta_kept_gap", "shared_gate_gap", "route_hist_gap",
            "td_gap"} <= set(sound)
    assert judge(sound, limits, quiet), exceeded(sound, limits)
    controls = rehearsed.control_numbers()
    assert set(controls) == {"fp8", "reset64"}
    for name, numbers in controls.items():
        assert exceeded(numbers, limits), name
        assert not judge(numbers, limits, quiet), name
    # a scan without memory leaves its own counter alone and moves what
    # follows it: the next layers' routing
    assert controls["reset64"]["delta_kept_gap"] < 1e-6
    assert controls["reset64"]["route_hist_gap"] > limits["route_hist_gap"]


def test_a_step_handed_back_unchanged_is_refused():
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false",
               PYTHONPATH=manifest.REPO, BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
         "4294967311", "--seconds", "2", "--trace", "0", "--rehearsal", "1",
         "--fault", "frozen_step"], cwd=manifest.REPO, env=env,
        capture_output=True, text=True, timeout=170)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "correct=false" in proc.stderr, proc.stderr[-3000:]
    assert "update_gap" in proc.stderr and "EXCEEDED" in proc.stderr
    # the new counters were compared, each beside its limit
    assert "[check] delta_kept_gap" in proc.stderr
    assert "[check] shared_gate_gap" in proc.stderr
    assert "[check] route_hist_gap" in proc.stderr
