"""The looped Ouro torso cell (``humanoid-ouro-ut4.learn-static``): its
configuration file against the published config, the catalog and the
program's own parameter tree, its driver's seeded weights and compared
numbers, both controls at rehearsal size, the operation counts its rooflines
and its share of the whole step's peak use against a hand count, and what the
manifest lists for it (the sound rehearsal of every cell, this one included,
is ``test_result_line.py``'s; the files found by name
``test_manifest_files.py``'s)."""

import json
import os

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes_loop

CELL = "humanoid-ouro-ut4.learn-static"
CONFIG = cellbuild.load_config("humanoid-ouro-ut4", False)
TORSO = CONFIG["model"]["torso"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FIRST_METRIC = "loop_chunk_device_ms"  # the first entry this cell brought
METRICS = [
    "loop_chunk_device_ms", "loop_attn_us_per_step", "loop_mlp_us_per_step",
    "loop_exit_us_per_step", "loop_attn_roofline", "loop_mlp_roofline",
    "loop_step_mfu", "exit_last_share", "exit_entropy_share"]

# the published widths, written out: the file may not drift from them
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 16, "num_key_value_heads": 16,
    "head_dim": 128, "intermediate_size": 5632, "hidden_act": "silu",
    "total_ut_steps": 4, "early_exit_threshold": 1, "rope_theta": 1000000,
    "rope_scaling": None, "rms_norm_eps": 1e-6, "vocab_size": 49152,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "model_type": "ouro",
}


@pytest.mark.parametrize("key, value", sorted(PUBLISHED.items()))
def test_every_width_is_as_published(key, value):
    assert CONFIG[key] == value
    if key in TORSO:
        assert TORSO[key] == value


def test_the_layers_reach_the_program_as_published():
    # published layers 1-8 of the top-level list, which is kept whole
    assert CONFIG["layer_types"] == ["full_attention"] * 48
    assert TORSO["layer_types"] == CONFIG["layer_types"][:8]
    assert TORSO["name"] == "ouro" and TORSO["sandwich_norm"] is True
    assert TORSO["num_experts"] == 0 and TORSO["experts_held"] == [0, 0]
    assert TORSO["num_dense_layers"] == len(TORSO["layer_types"]) == 8
    assert "qk_norm" not in TORSO and "sliding_window" not in TORSO
    assert TORSO["rope_parameters"] == {"full_attention": {
        "rope_type": "default", "rope_theta": CONFIG["rope_theta"]}}
    assert TORSO["exit_entropy_beta"] == 0.05
    assert TORSO["tokens"] == CONFIG["model"]["obs_dim"] == 4096
    assert 10 * (376 + 17) == 3930 <= 4096
    assert TORSO["vocab_rows"] == CONFIG["vocab_size"]  # held whole
    assert CONFIG["model"]["compute_dtype"] == "bfloat16"
    assert CONFIG["model"]["lr_critic"] == 3e-5
    assert CONFIG["learner"] == {**CONFIG["learner"], "batch_size": 2, "k": 1}
    assert CONFIG["replay"]["capacity"] == 32768
    # the rehearsal: at least two layers, three passes, the second norms on
    small = cellbuild.load_config("humanoid-ouro-ut4", True)["model"]["torso"]
    assert len(small["layer_types"]) >= 2 and small["total_ut_steps"] == 3
    assert small["sandwich_norm"] is True and small["num_experts"] == 0
    assert small["num_key_value_heads"] == small["num_attention_heads"]


def test_the_cut_is_written_down_and_keeps_the_floors():
    assert CONFIG["reduced"] == ["num_hidden_layers", "lm_head"]
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 48
    assert CONFIG["num_hidden_layers"] == len(TORSO["layer_types"]) == 8 >= 4
    assert CONFIG["lm_head"] is False and "lm_head" in pub
    for said in ("first of six pipeline stages", "layers 1-8 of 48",
                 "40 layers left out", "closes the loop"):
        assert said in CONFIG["stands_for"], said
    assert "10.26 GB" in CONFIG["reduced_why"]
    for text in (CONFIG["limits_why"], CONFIG["reduced_why"],
                 *CONFIG["assumed"]):
        assert text and "PLACEHOLDER" not in text
    assert "detach" in CONFIG["limits_why"] and "fp8" in CONFIG["limits_why"]
    for marked in ("input_layernorm_2", "post_attention_layernorm_2",
                   "OuroModel.forward", "early_exit_gate",
                   "lambda_4 is not used", "later-stage value",
                   "pass 4's", "10 Humanoid-v4 steps", "i paired with i + 64",
                   "0.5 / 0.25 / 0.125 / 0.125", "held whole"):
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
    # ISSUE 41's arithmetic, leaf by leaf
    assert set(torso) == {"embed", "final_norm", "exit_gate"} | {
        f"layer_{i}" for i in range(8)}
    for i in range(8):
        lay = torso[f"layer_{i}"]
        assert sum(size(lay[n]) for n in ("q", "k", "v", "o")) \
            == here["attention_a_layer"] == 4 * 2048 * 2048 == 16777216
        assert sum(size(lay[n]) for n in ("w1", "w3", "w2")) \
            == here["swiglu_a_layer"] == 3 * 2048 * 5632 == 34603008
        assert sum(size(lay[n]) for n in (
            "attn_norm", "op_post_norm", "mlp_norm", "ff_post_norm")) \
            == here["norms_a_layer"] == 8192
        assert size(lay) == here["layer"] == 51388416
    assert here["layers"] == 8 * 51388416 == 411107328
    assert size(torso["embed"]) == here["embedding"] == 49152 * 2048
    assert size(torso["final_norm"]) == here["final_norm"] == 2048
    assert size(torso["exit_gate"]) == here["exit_gate"] == 2049
    assert size(state.critic_params) + size(state.actor_params) \
        == here["total"] == here["torso"] + here["heads"] == 513108805
    assert 10.2e9 < 20 * here["total"] < 10.3e9
    # over the floor of a quarter of the chip's 16.9 GB, under the chip
    assert 0.25 < 20 * here["total"] / 16.9e9 < 0.7
    # the ring the file states: 32,768 rows of two 4,096-wide fields
    row = 4 * (2 * 4096 + 17 + 3)
    assert row == 32848 and 1.0e9 < row * CONFIG["replay"]["capacity"] \
        < 1.1e9
    # the kept layer boundaries of reduced_why: passes x layers x batch
    assert 4 * 8 * 2 * 4096 * 2048 * 4 == 2147483648


def test_the_file_holds_every_key_of_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f if "Ouro-2.6B" in line)
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    # no width among the cuts
    assert not [k for k in CONFIG["reduced"] if k.endswith(
        ("_size", "_dim", "_rank", "per_tok"))]


def test_seeded_leaves_are_at_their_own_fan_in():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import learner_static_loop as driver

    cfg = cellbuild.load_config("humanoid-ouro-ut4", True)
    config = cellbuild.learner_config(cfg)
    make = jax.jit(lambda s: driver.seeded_params(config, s))
    _actor, critic = make(jnp.uint32(12345))
    torso = critic["params"]["torso"]
    std = lambda x: float(jnp.std(x))  # noqa: E731
    assert std(torso["embed"]["kernel"]) == pytest.approx(1.0, rel=0.1)
    assert std(torso["layer_0"]["q"]["kernel"]) == pytest.approx(
        1 / 8, rel=0.1)
    assert std(torso["layer_1"]["w2"]["kernel"]) == pytest.approx(
        96 ** -0.5, rel=0.1)
    gate = torso["exit_gate"]
    assert gate["kernel"].shape == (64, 1)
    assert std(gate["kernel"]) == pytest.approx(1 / 8, rel=0.3)
    assert float(gate["bias"][0]) == 0.0
    for name in ("attn_norm", "op_post_norm", "mlp_norm", "ff_post_norm"):
        assert np.all(np.asarray(torso["layer_0"][name]["scale"]) == 1.0)
    other = make(jnp.uint32(54321))[1]["params"]["torso"]
    again = make(jnp.uint32(12345))[1]["params"]["torso"]
    k = lambda t: np.asarray(t["exit_gate"]["kernel"])  # noqa: E731
    assert not np.array_equal(k(other), k(torso))
    np.testing.assert_array_equal(k(again), k(torso))
    assert driver.CELL is driver.LoopCell
    assert driver.COUNTERS == ("exit_dist", "loss_by_pass")


def test_the_gaps_of_the_counters_and_of_the_embeddings_moment():
    from benchmark.drivers.learner_static_loop import (
        counter_gap,
        embed_moment_gap,
    )

    ref = np.asarray([[0.5, 0.25, 0.125, 0.125]])
    assert counter_gap(ref, ref) == 0.0
    assert counter_gap(ref * [1.0, 1.01, 0.98, 1.0], ref) \
        == pytest.approx(0.02)
    assert counter_gap(ref.astype(np.float32), ref) < 1e-7
    tree = lambda a: {"params": {"torso": {"embed": {  # noqa: E731
        "kernel": np.asarray(a, np.float32)}}}}
    g = np.asarray([[3.0, 4.0], [0.0, 0.0]])
    assert embed_moment_gap(tree(g), tree(g)) == 0.0
    # a gradient of the right size that points another way is seen
    assert embed_moment_gap(tree(-g), tree(g)) == pytest.approx(2.0)
    assert embed_moment_gap(tree(0.5 * g), tree(g)) == pytest.approx(0.5)
    assert embed_moment_gap(tree(0 * g), tree(g)) == pytest.approx(1.0)


def test_attention_swiglu_and_step_counts_against_a_hand_count():
    t_len, d, f, batch = 4096, 2048, 5632, 2
    assert shapes_loop.applications(TORSO) == 4 * 8 == 32
    pairs = shapes_loop.causal_pairs(t_len)
    assert pairs == t_len * (t_len + 1) // 2
    # one layer application, one sequence, one forward pass, by hand
    attn = 2 * (t_len * 4 * d * d + pairs * 16 * 128 * 2)
    got = shapes_loop.attention_counts(TORSO, batch)
    assert got["flops"] == pytest.approx(5 * 32 * batch * attn)
    assert got["bytes"] == pytest.approx(5 * 32 * (
        2 * 4 * d * d + batch * t_len * (2 * 4 * d + 2 * 2 * 2 * d)))
    mlp = 2 * t_len * 3 * d * f
    got = shapes_loop.mlp_counts(TORSO, batch)
    assert got["flops"] == pytest.approx(5 * 32 * batch * mlp)
    assert got["bytes"] == pytest.approx(5 * 32 * (
        2 * 3 * d * f + batch * t_len * (2 * 4 * d + 2 * 3 * f)))
    # ISSUE 41's token-and-application count: 119.6 MFLOP forward, of which
    # SwiGLU 69.2, projections 33.6, causal scores and values 16.8
    a_token = (attn + mlp) / t_len
    assert a_token == pytest.approx(119.6e6, rel=1e-3)
    assert mlp / t_len == pytest.approx(69.2e6, rel=1e-3)
    assert 2 * 4 * d * d == pytest.approx(33.6e6, rel=2e-3)
    assert 2 * pairs * 16 * 128 * 2 / t_len == pytest.approx(16.8e6, rel=2e-3)
    # 156.8 TFLOP a step, 0.80 s at the chip's 197 TFLOP/s
    step = shapes_loop.step_flops(TORSO, batch)
    assert step == pytest.approx(156.8e12, rel=1e-3)
    assert step / 197e12 == pytest.approx(0.80, abs=0.005)
    # brute force at a small size: every position and pair
    small = {**TORSO, "tokens": 40, "total_ut_steps": 3,
             "layer_types": ["full_attention"] * 2}
    flops = 0
    for t in range(40):
        flops += 2 * 4 * d * d
        for _s in range(t + 1):
            flops += 2 * 16 * 128 * 2  # one score and one weighted value
    assert shapes_loop.attention_counts(small, 1)["flops"] \
        == pytest.approx(5 * 6 * flops)
    assert shapes_loop.mlp_counts(small, 1)["flops"] \
        == pytest.approx(5 * 6 * 40 * 2 * 3 * d * f)
    # the exit shares
    seeded = [[0.5, 0.25, 0.125, 0.125]]
    assert shapes_loop.exit_last_share(seeded) == pytest.approx(12.5)
    assert shapes_loop.exit_entropy_share(seeded) == pytest.approx(
        100 * 1.75 * np.log(2) / np.log(4))
    assert shapes_loop.exit_entropy_share([[0.25] * 4]) \
        == pytest.approx(100.0)
    assert shapes_loop.exit_entropy_share([[0, 0, 0, 1.0]]) == 0.0
    assert shapes_loop.exit_last_share([[0, 0, 0, 1.0]]) == 100.0
    assert shapes_loop.exit_last_share(
        [[0.5, 0.5, 0, 0], [0, 0, 0.5, 0.5]]) == pytest.approx(25.0)


def test_the_cell_is_one_chip_and_lists_its_nine_layer_metrics():
    man = manifest.load()
    assert manifest.cell(man, CELL)["chips"] == 1
    assert manifest.cell(man, CELL)["traffic"] == "learn-static-loop"
    traced = manifest.metrics_for(man, CELL, True)
    assert set(traced) == {"compile_s", *METRICS}
    assert set(manifest.metrics_for(man, CELL, False)) == {
        "grad_steps_per_s", "setup_s"}
    # the other torso cells' readers are not asked in this cell, nor this
    # cell's in theirs
    for other in ("humanoid-mellum2-ep4.learn-static",
                  "humanoid-keye2-ep8.learn-static",
                  "humanoid-lfm2-ep4.learn-static",
                  "humanoid-qwen3next-ep32.learn-static"):
        theirs = manifest.metrics_for(man, other, True)
        assert set(theirs) & set(traced) == {"compile_s"}
    for entry in traced.values():
        if entry["name"] != "compile_s":
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "grad_steps_per_s"
            if entry["name"].endswith(("_roofline", "_mfu")):
                assert entry["unit"] == "%" and entry["better"] == "higher"
    assert traced["loop_step_mfu"]["layer"] == "fused chunk"
    assert traced["exit_last_share"]["source"] == "program_counter"
    # the entries this cell brought stand together and in order, from the
    # first of them on: whatever a later PR appends comes behind them
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(FIRST_METRIC)
    assert names[at:at + len(METRICS)] == METRICS
    assert names[at - 1] == "linear_expert_load_max_over_mean"
    cells = [w["name"] for w in man["workloads"]]
    assert cells.index(CELL) == cells.index(
        "humanoid-qwen3next-ep32.learn-static") + 1
    configs = [c["name"] for c in man["configs"]]
    assert configs.index("humanoid-ouro-ut4") == configs.index(
        "humanoid-qwen3next-ep32") + 1
    entry = man["configs"][configs.index("humanoid-ouro-ut4")]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]


def test_the_readers_read_this_cell_and_no_other():
    """On a context that is another cell's (``torso``, ``sparse``, ``hybrid``
    or ``linear``, not ``loop``) every reader of this cell returns nothing,
    and the other cells' roofline readers return nothing on this cell's."""
    from benchmark import (
        hybrid_trace,
        linear_trace,
        loop_trace,
        run,
        sparse_trace,
        torso_trace,
    )

    log = lambda _m: None  # noqa: E731
    dist = np.asarray([[0.5, 0.25, 0.125, 0.125]])
    for key in ("torso", "sparse", "hybrid", "linear"):
        theirs = {"log": log, "trace": object(), key: TORSO, "k": 1,
                  "chunk_text": "", "chunk_program": "jit_fn",
                  "torso_trace": None, "sparse_trace": None,
                  "hybrid_trace": None, "linear_trace": None,
                  "batch_size": 2, "exit_dist": dist,
                  "peak": {"bf16_flops_per_s": 197e12,
                           "hbm_bytes_per_s": 819e9}}
        for name in METRICS:
            assert run.layer_reader(name)(dict(theirs)) is None, name
    mine = {"log": log, "trace": object(), "loop": TORSO, "k": 1,
            "loop_trace": None, "batch_size": 2}
    assert torso_trace.attn_roofline(dict(mine)) is None
    assert sparse_trace.attention_roofline(dict(mine)) is None
    assert hybrid_trace.conv_roofline(dict(mine)) is None
    assert linear_trace.delta_scan_roofline(dict(mine)) is None
    assert loop_trace.mlp_roofline(dict(mine)) is None  # no trace read
    assert loop_trace.step_mfu(dict(mine)) is None
    assert loop_trace.exit_last_share({**mine, "exit_dist": dist}) \
        == pytest.approx(12.5)
    assert loop_trace.exit_entropy_share({**mine, "exit_dist": dist}) \
        == pytest.approx(87.5)
    # a program without the counter (the parent's) gives the readers nothing
    assert loop_trace.exit_last_share({**mine, "exit_dist": None}) is None
    assert loop_trace.exit_entropy_share({**mine, "exit_dist": None}) is None
    # an untraced run neither
    assert loop_trace.exit_last_share(
        {**mine, "trace": None, "exit_dist": dist}) is None


def test_the_scopes_are_read_from_a_chunk_programs_text():
    """``loop_trace.analyse`` on a hand-made trace: each scope's time goes to
    its own metric, a roofline is the least time over the time spent, the
    step's share of the peak is over the whole chunk."""
    from benchmark import loop_trace, program_trace

    assert {"torso.attn_full", "torso.mlp", "torso.exit"} \
        <= set(loop_trace.LOOP_SCOPES)
    assert set(program_trace.TOP_SCOPES) <= set(loop_trace.ALL_SCOPES)
    found = {"total": 3.0, "covered": 1.0, "step": {
        s: 0.0 for s in loop_trace.ALL_SCOPES}}
    found["step"].update({"torso.attn_full": 1.2, "torso.mlp": 1.0})
    ctx = {"log": lambda _m: None, "loop": TORSO, "loop_trace": found,
           "batch_size": 2, "trace": object(), "k": 1,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert loop_trace.scope_us(ctx, "torso.mlp") == pytest.approx(1e6)
    assert loop_trace.scope_us(ctx, "torso.exit") == 0.0
    assert loop_trace.chunk_ms(ctx) == 3000.0
    attn = shapes_loop.attention_counts(TORSO, 2)
    mlp = shapes_loop.mlp_counts(TORSO, 2)
    # both are bound by their FLOPs
    for counts in (attn, mlp):
        assert counts["flops"] / 197e12 > counts["bytes"] / 819e9
    assert loop_trace.attention_roofline(ctx) == pytest.approx(
        100 * attn["flops"] / 197e12 / 1.2)
    assert loop_trace.mlp_roofline(ctx) == pytest.approx(
        100 * mlp["flops"] / 197e12 / 1.0)
    assert loop_trace.step_mfu(ctx) == pytest.approx(
        100 * (attn["flops"] + mlp["flops"]) / 197e12 / 3.0)
    assert loop_trace.step_mfu({**ctx, "k": 2}) == pytest.approx(
        2 * loop_trace.step_mfu(ctx))
    # a scope no operation carries reads 0.0, not a division by zero
    assert loop_trace.roofline(ctx, {"flops": 1.0, "bytes": 1.0}, "x",
                               "torso.exit") == 0.0


@pytest.fixture(scope="module")
def rehearsed():
    """The cell at rehearsal size, its first chunk run and its program
    given up: what ``benchmark/tools/calibrate_controls.py`` does a seed."""
    import time

    from benchmark.drivers import learner_static_loop as driver
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
    reference with fp8 product inputs, and the reference with a stop-gradient
    between passes, each break at least one of them."""
    from benchmark.learner import judge
    from benchmark.tools.calibrate_controls import exceeded

    limits = rehearsed.env.cfg["limits"]
    quiet = lambda _m: None  # noqa: E731
    controls = rehearsed.control_numbers()
    sound = rehearsed.check_first_chunk()
    print("SOUND", sound)
    print("CONTROLS", controls)
    assert {"exit_dist_gap", "loss_by_pass_gap", "embed_moment_gap",
            "td_gap", "moment_gap"} <= set(sound)
    assert judge(sound, limits, quiet), exceeded(sound, limits)
    assert set(controls) == {"fp8", "detach"}
    for name, numbers in controls.items():
        assert exceeded(numbers, limits), name
        assert not judge(numbers, limits, quiet), name
    # a backward that stops at a pass's edge leaves every forward number of
    # the first step alone and moves the gradients
    detach = controls["detach"]
    assert detach["td_gap"] < 1e-6
    assert detach["embed_moment_gap"] > limits["embed_moment_gap"]
    assert "embed_moment_gap" in exceeded(detach, limits)


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
    # the new numbers were compared, each beside its limit
    assert "[check] exit_dist_gap" in proc.stderr
    assert "[check] loss_by_pass_gap" in proc.stderr
    assert "[check] embed_moment_gap" in proc.stderr
