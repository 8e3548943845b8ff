"""The LFM2 torso cell (``humanoid-lfm2-ep4.learn-static``): its configuration
file against the published config, the catalog and the program's own
parameter tree, its driver's seeded weights and compared numbers, the
operation counts its rooflines use against brute force, and what the
manifest lists for it (the sound rehearsal of every cell, this one included,
is ``test_result_line.py``'s; the files found by name
``test_manifest_files.py``'s)."""

import json
import os

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes_hybrid

CELL = "humanoid-lfm2-ep4.learn-static"
CONFIG = cellbuild.load_config("humanoid-lfm2-ep4", False)
TORSO = CONFIG["model"]["torso"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the published widths, written out: the file may not drift from them
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "intermediate_size": 7168, "moe_intermediate_size": 1792,
    "num_experts": 32, "num_experts_per_tok": 4, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "rope_theta": 1000000, "vocab_size": 65536,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
}


@pytest.mark.parametrize("key, value", sorted(PUBLISHED.items()))
def test_every_width_is_as_published(key, value):
    assert CONFIG[key] == value
    if key in TORSO:
        assert TORSO[key] == value


def test_the_layers_reach_the_program_as_published():
    # published layers 1-5 of the top-level list, which is kept whole
    assert len(CONFIG["layer_types"]) == 24
    assert CONFIG["layer_types"].count("conv") == 18
    assert TORSO["layer_types"] == CONFIG["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert TORSO["head_dim"] == 2048 // 32 == 64
    assert TORSO["rms_norm_eps"] == CONFIG["norm_eps"]
    assert TORSO["rope_parameters"] == {"full_attention": {
        "rope_type": "default", "rope_theta": CONFIG["rope_theta"]}}
    assert TORSO["router_scores"] == "sigmoid" and TORSO["qk_norm"] is True
    assert TORSO["bias_update_rate"] == 1e-3
    assert TORSO["tokens"] == CONFIG["model"]["obs_dim"] == 8192
    assert 20 * (376 + 17) == 7860 <= 8192
    # the rehearsal has all three kinds of layer
    small = cellbuild.load_config("humanoid-lfm2-ep4", True)["model"]["torso"]
    assert small["layer_types"] == ["conv", "full_attention", "conv"]
    assert small["num_dense_layers"] == 1 and small["use_expert_bias"]


def test_the_cut_is_written_down_and_keeps_the_floors():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "experts_held", "vocab", "lm_head"]
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 24 and pub["num_dense_layers"] == 2
    assert pub["num_experts"] == 32 and pub["vocab_size"] == 65536
    assert CONFIG["num_hidden_layers"] == len(TORSO["layer_types"]) == 5
    # leading dense layers count once; a whole period follows
    assert CONFIG["num_dense_layers"] == TORSO["num_dense_layers"] == 1
    lo, hi = CONFIG["experts_held"]
    assert TORSO["experts_held"] == [lo, hi] and hi - lo == 8 >= 8
    assert CONFIG["vocab"] == TORSO["vocab_rows"] == 65536 // 4
    assert CONFIG["vocab"] >= 65536 // 8 and TORSO["bins"] == 1024
    assert CONFIG["lm_head"] is False
    assert "one chip of four" in CONFIG["stands_for"]
    assert "19 layers left out" in CONFIG["stands_for"]
    assert CONFIG["limits_why"] and "PLACEHOLDER" not in CONFIG["limits_why"]
    for marked in ("b, c, u in that order", "1e-6", "DeepSeek-V3",
                   "seeded bias is non-zero", "20 Humanoid-v4 steps",
                   "head size 64", "q_layernorm", "the taps' is 3"):
        assert any(marked in line for line in CONFIG["assumed"]), marked


def test_parameters_here_are_the_programs_own_tree():
    import jax

    from d4pg_tpu.learner import init_state

    config = cellbuild.learner_config(CONFIG)
    state = jax.eval_shape(lambda: init_state(config, jax.random.key(0)))
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa
    here = CONFIG["parameters_here"]
    torso = state.critic_params["params"]["torso"]
    assert size(torso) == here["torso"]
    # ISSUE 34's arithmetic, leaf by leaf
    conv, attn = torso["layer_0"], torso["layer_1"]
    assert sum(size(conv[n]) for n in ("in_proj", "conv", "out_proj")) \
        == here["conv_operator"] == 2048 * 6144 + 2048 * 3 + 2048 * 2048
    assert sum(size(attn[n]) for n in ("q", "k", "v", "o", "q_norm",
                                       "k_norm")) \
        == here["attention_operator"] == 10485888
    assert sum(size(conv[n]) for n in ("w1", "w3", "w2")) \
        == here["dense_feed_forward"] == 3 * 2048 * 7168
    assert sum(size(attn[n]) for n in ("gate", "up", "down")) \
        == here["experts_a_layer"] == 8 * 3 * 2048 * 1792
    assert size(attn["router"]) == 2048 * 32 + 32
    assert size(torso["layer_0"]) == here["conv_dense_layer"] == 60827648
    assert size(torso["layer_1"]) == here["attention_expert_layer"] \
        == 98635936
    for i in (2, 3, 4):
        assert size(torso[f"layer_{i}"]) == here["conv_expert_layer"] \
            == 104933408
    assert size(state.critic_params) + size(state.actor_params) \
        == here["total"] == here["torso"] + here["heads"]
    assert 10.1e9 < 20 * here["total"] < 10.3e9
    # over the floor of a quarter of the chip's 16.9 GB, under the chip
    assert 0.25 < 20 * here["total"] / 16.9e9 < 0.7
    # the ring the file states: 32,768 rows of two 8,192-wide fields
    row = 4 * (2 * 8192 + 17 + 3)
    assert row == 65616 and 2.1e9 < row * CONFIG["replay"]["capacity"] \
        < 2.2e9


def test_the_file_holds_every_key_of_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if "LFM2-8B-A1B" in line)
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    # no width among the cuts
    assert not [k for k in CONFIG["reduced"] if k.endswith(
        ("_size", "_dim", "_rank", "per_tok"))]


def test_seeded_taps_and_biases_are_put_right_by_the_driver():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import learner_static_hybrid as driver

    cfg = cellbuild.load_config("humanoid-lfm2-ep4", True)
    config = cellbuild.learner_config(cfg)
    make = jax.jit(lambda s: driver.seeded_params(cfg, config, s))
    _actor, critic = make(jnp.uint32(12345))
    layers = critic["params"]["torso"]
    std = lambda x: float(jnp.std(x))  # noqa: E731
    # every leaf at its own fan-in: the taps' is 3, an expert's its rows
    assert std(layers["layer_0"]["conv"]["kernel"]) == pytest.approx(
        3 ** -0.5, rel=0.15)
    assert std(layers["layer_0"]["in_proj"]["kernel"]) == pytest.approx(
        1 / 8, rel=0.1)
    assert std(layers["layer_1"]["gate"]["kernel"]) == pytest.approx(
        1 / 8, rel=0.1)
    assert std(layers["layer_0"]["w2"]["kernel"]) == pytest.approx(
        96 ** -0.5, rel=0.1)
    gamma, steps = 1e-3, cfg["seeded_bias_steps"]
    seen = set()
    for name in ("layer_1", "layer_2"):
        bias = np.asarray(layers[name]["router"]["bias"], np.float64)
        whole = np.round(bias / gamma)
        np.testing.assert_allclose(bias, whole * gamma, atol=1e-9)
        assert np.abs(whole).max() <= steps and np.abs(whole).sum() > 0
        seen.add(tuple(whole.tolist()))
    assert len(seen) == 2  # a layer's biases are its own
    assert "bias" not in layers["layer_1"]["q"]
    # another seed, other biases; the same seed, the same
    other = make(jnp.uint32(54321))[1]["params"]["torso"]
    again = make(jnp.uint32(12345))[1]["params"]["torso"]
    b = lambda t: np.asarray(t["layer_1"]["router"]["bias"])  # noqa: E731
    assert not np.array_equal(b(other), b(layers))
    np.testing.assert_array_equal(b(again), b(layers))
    assert driver.CELL is driver.HybridCell
    assert driver.biases(critic).keys() == {"layer_1", "layer_2"}


def test_bias_gap_is_the_share_of_biases_that_stepped_another_way():
    from benchmark.drivers.learner_static_hybrid import bias_gap

    gamma = 1e-3
    start = {f"layer_{i}": gamma * np.arange(-16, 16.0) for i in (1, 2, 3, 4)}
    step = np.where(np.arange(32) % 2, 1.0, -1.0) * gamma
    ref = {k: v + step for k, v in start.items()}
    assert bias_gap(ref, ref, start, gamma) == 0.0
    # float32 rounding of the sum is no step
    near = {k: (v + step).astype(np.float32).astype(np.float64)
            for k, v in start.items()}
    assert bias_gap(near, ref, start, gamma) == 0.0
    prog = {k: v.copy() for k, v in ref.items()}
    prog["layer_2"][5] -= 2 * gamma  # the other sign
    prog["layer_4"][0] += gamma  # a count at the mean on one side: sign 0
    assert bias_gap(prog, ref, start, gamma) == pytest.approx(2 / 128)
    # a state handed back unchanged differs wherever the reference moved
    assert bias_gap(start, ref, start, gamma) == 1.0


def test_conv_attention_and_expert_counts_against_brute_force():
    # one conv layer, one sequence, one forward pass, by hand; four conv
    # layers, five forward-equivalents, batch 4 in the functions
    t_len, d = 8192, 2048
    conv = 2 * t_len * (d * 3 * d + d * d)
    got = shapes_hybrid.conv_counts(TORSO, 4)
    assert got["flops"] == pytest.approx(5 * 4 * 4 * conv)
    assert got["bytes"] == pytest.approx(5 * 4 * 4 * 2 * t_len * 4 * d)
    pairs = shapes_hybrid.causal_pairs(t_len)
    assert pairs == 33558528
    attn = 2 * (t_len * (2 * d * 2048 + 2 * d * 512) + pairs * 32 * 64 * 2)
    got = shapes_hybrid.attention_counts(TORSO, 4)
    assert got["flops"] == pytest.approx(5 * 1 * 4 * attn)
    # brute force at a small size: a loop over every position and pair
    small = {**TORSO, "tokens": 40, "layer_types": [
        "conv", "full_attention", "conv"]}
    flops = 0
    for t in range(40):
        flops += 2 * d * 3 * d + 2 * d * d  # in_proj and out_proj, one row
    assert shapes_hybrid.conv_counts(small, 1)["flops"] \
        == pytest.approx(5 * 2 * flops)
    flops = 2 * 40 * (2 * d * 2048 + 2 * d * 512)
    for t in range(40):
        for _s in range(t + 1):
            flops += 2 * 32 * 64 * 2  # one score and one weighted value
    assert shapes_hybrid.attention_counts(small, 1)["flops"] \
        == pytest.approx(5 * flops)
    # experts: an even load is 4,096 assignments an expert and a layer
    counts = np.full((1, 4, 32), 4096, np.int64)
    rows = shapes_hybrid.held_assignments(TORSO, counts)
    assert rows == 4 * 8 * 4096 and shapes_hybrid.expert_layers(TORSO) == 4
    got = shapes_hybrid.expert_counts(TORSO, rows)
    assert got["flops"] == pytest.approx(5 * 2 * rows * 3 * 2048 * 1792)
    assert got["bytes"] == pytest.approx(5 * 2 * (
        4 * 8 * 3 * 2048 * 1792 + rows * (2 * 2048 + 3 * 1792)))
    assert shapes_hybrid.load_max_over_mean(TORSO, counts) == 1.0
    # the step's 59.9 TFLOP of ISSUE 34, of which these three and the dense
    # layer are all but the heads and the router
    dense = 5 * 4 * 2 * t_len * 3 * d * 7168
    total = shapes_hybrid.conv_counts(TORSO, 4)["flops"] \
        + shapes_hybrid.attention_counts(TORSO, 4)["flops"] \
        + shapes_hybrid.expert_counts(TORSO, rows)["flops"] + dense
    assert 58e12 < total < 61e12
    swapped = np.asarray([[1311, 2621, 1311, 0]])
    assert shapes_hybrid.swapped_share(TORSO, swapped, 4) \
        == pytest.approx(100 * 1310.75 / 131072)


def test_the_cell_is_one_chip_and_lists_its_eleven_layer_metrics():
    man = manifest.load()
    assert manifest.cell(man, CELL)["chips"] == 1
    traced = manifest.metrics_for(man, CELL, True)
    assert set(traced) == {
        "compile_s", "hybrid_chunk_device_ms", "conv_us_per_step",
        "dense_mlp_us_per_step", "hybrid_attn_us_per_step",
        "hybrid_route_us_per_step", "hybrid_experts_us_per_step",
        "conv_roofline", "hybrid_attn_roofline", "hybrid_experts_roofline",
        "bias_swapped_share", "hybrid_expert_load_max_over_mean"}
    assert set(manifest.metrics_for(man, CELL, False)) == {
        "grad_steps_per_s", "setup_s"}
    # the other torso cells' readers are not asked in this cell, nor this
    # cell's in theirs
    for other in ("humanoid-mellum2-ep4.learn-static",
                  "humanoid-keye2-ep8.learn-static"):
        theirs = manifest.metrics_for(man, other, True)
        assert set(theirs) & set(traced) == {"compile_s"}
    for entry in traced.values():
        if entry["name"] != "compile_s":
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "grad_steps_per_s"
            if entry["name"].endswith("_roofline"):
                assert entry["unit"] == "%" and entry["better"] == "higher"
    # the new entries are at the end of their lists
    assert man["workloads"][-1]["name"] == CELL
    assert man["configs"][-1]["name"] == "humanoid-lfm2-ep4"
    assert [m["name"] for m in man["per_layer"]][-11:] == [
        "hybrid_chunk_device_ms", "conv_us_per_step",
        "dense_mlp_us_per_step", "hybrid_attn_us_per_step",
        "hybrid_route_us_per_step", "hybrid_experts_us_per_step",
        "conv_roofline", "hybrid_attn_roofline", "hybrid_experts_roofline",
        "bias_swapped_share", "hybrid_expert_load_max_over_mean"]


def test_the_readers_read_this_cell_and_no_other():
    """On a context that is another cell's (``torso`` or ``sparse``, not
    ``hybrid``) every reader of this cell returns nothing, and the other
    cells' roofline readers return nothing on this cell's."""
    from benchmark import hybrid_trace, run, sparse_trace, torso_trace

    log = lambda _m: None  # noqa: E731
    for key in ("torso", "sparse"):
        theirs = {"log": log, "trace": object(), key: TORSO, "k": 1,
                  "chunk_text": "", "chunk_program": "jit_fn",
                  "torso_trace": None, "sparse_trace": None, "batch_size": 4,
                  "route_counts": np.ones((1, 4, 32))}
        for name in manifest.metrics_for(manifest.load(), CELL, True):
            if name != "compile_s":
                assert run.layer_reader(name)(dict(theirs)) is None, name
    mine = {"log": log, "trace": object(), "hybrid": TORSO, "k": 1,
            "hybrid_trace": None, "batch_size": 4}
    assert torso_trace.attn_roofline(dict(mine)) is None
    assert sparse_trace.attention_roofline(dict(mine)) is None
    assert hybrid_trace.conv_roofline(dict(mine)) is None  # no trace read
    swapped = np.asarray([[1311, 2621, 1311, 0]])
    assert hybrid_trace.swapped_share({**mine, "bias_swapped": swapped}) \
        == pytest.approx(1.0, abs=1e-3)
    counts = np.full((1, 4, 32), 4096)
    assert hybrid_trace.load_max_over_mean(
        {**mine, "route_counts": counts}) == 1.0
    # a program without the counter (the parent's) gives the reader nothing
    assert hybrid_trace.swapped_share({**mine, "bias_swapped": None}) is None


def test_the_scopes_are_read_from_a_chunk_programs_text():
    """``hybrid_trace.analyse`` on a hand-made trace: each scope's time goes
    to its own metric, a roofline is the least time over the time spent."""
    from benchmark import hybrid_trace, program_trace

    assert {"torso.conv", "torso.mlp", "torso.attn_full", "torso.route",
            "torso.experts"} <= set(hybrid_trace.HYBRID_SCOPES)
    assert set(program_trace.TOP_SCOPES) <= set(hybrid_trace.ALL_SCOPES)
    found = {"total": 1.0, "covered": 1.0, "step": {
        s: 0.0 for s in hybrid_trace.ALL_SCOPES}}
    found["step"].update({"torso.conv": 0.2, "torso.attn_full": 0.05})
    ctx = {"log": lambda _m: None, "hybrid": TORSO, "hybrid_trace": found,
           "batch_size": 4, "trace": object(),
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert hybrid_trace.scope_us(ctx, "torso.conv") == pytest.approx(2e5)
    assert hybrid_trace.chunk_ms(ctx) == 1000.0
    flops = shapes_hybrid.conv_counts(TORSO, 4)["flops"]
    assert hybrid_trace.conv_roofline(ctx) == pytest.approx(
        100 * flops / 197e12 / 0.2)
    # a scope no operation carries reads 0.0, not a division by zero
    assert hybrid_trace.roofline(ctx, {"flops": 1.0, "bytes": 1.0}, "x",
                                 "torso.mlp") == 0.0


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
    # the bias's own number was compared, beside its limit, and failed too:
    # a state handed back has moved no bias
    line = next(ln for ln in proc.stderr.splitlines()
                if ln.startswith("[check] bias_gap"))
    assert "EXCEEDED" in line, line
    assert "[check] route_hist_gap" in proc.stderr
