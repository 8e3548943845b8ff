"""The Nemotron-H torso cell (``humanoid-nemotronh-ep16.learn-static``): its
configuration file against the published config, the catalog and the program's
own parameter tree, its driver's seeded weights and compared numbers, both
controls at rehearsal size, the operation counts its rooflines use against the
reference's own products and brute force, and what the manifest lists for it
(the sound rehearsal of every cell, this one included, is
``test_result_line.py``'s; the files found by name
``test_manifest_files.py``'s).
"""

import json
import os

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes_ssm

CELL = "humanoid-nemotronh-ep16.learn-static"
CONFIG = cellbuild.load_config("humanoid-nemotronh-ep16", False)
TORSO = CONFIG["model"]["torso"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FIRST_METRIC = "ssm_chunk_device_ms"  # the first entry this cell brought
METRICS = [
    "ssm_chunk_device_ms", "mamba_us_per_step", "ssd_scan_us_per_step",
    "ssm_attn_us_per_step", "ssm_shared_expert_us_per_step",
    "ssm_route_us_per_step", "ssm_experts_us_per_step", "mamba_roofline",
    "ssd_scan_roofline", "ssm_attn_roofline", "ssm_experts_roofline",
    "ssm_step_mfu", "ssd_kept_share", "ssm_bias_swapped_share",
    "ssm_expert_load_max_over_mean"]

# the published widths, written out: the file may not drift from them
PUBLISHED = {
    "hidden_size": 2688, "num_attention_heads": 32, "num_key_value_heads": 2,
    "head_dim": 128, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4, "chunk_size": 128,
    "expand": 2, "n_routed_experts": 128, "num_experts_per_tok": 6,
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "mlp_hidden_act": "relu2",
    "use_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
    "norm_eps": 1e-5, "layer_norm_epsilon": 1e-5, "vocab_size": 131072,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "model_type": "nemotron_h", "max_position_embeddings": 262144,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
}


@pytest.mark.parametrize("key, value", sorted(PUBLISHED.items()))
def test_every_width_is_as_published(key, value):
    assert CONFIG[key] == value
    if key in TORSO and key != "hybrid_override_pattern":
        assert TORSO[key] == value


def test_the_blocks_reach_the_program_as_published():
    # published layers 1-7: the pattern's own first seven characters
    assert TORSO["hybrid_override_pattern"] == "MEMEM*E" \
        == CONFIG["hybrid_override_pattern"][:7]
    assert "layer_types" not in TORSO and "rope_parameters" not in TORSO
    pattern = CONFIG["hybrid_override_pattern"]
    assert len(pattern) == 52 == CONFIG["published"]["num_hidden_layers"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (23, 23, 6)
    assert pattern == "MEMEM*" + 4 * "EMEMEM*" + "EMEMEMEM*" + "EMEMEMEME"
    assert TORSO["name"] == "nemotronh"
    assert TORSO["num_experts"] == CONFIG["n_routed_experts"]
    assert TORSO["shared_expert_intermediate_size"] \
        == CONFIG["moe_shared_expert_intermediate_size"] \
        == 2 * TORSO["moe_intermediate_size"]
    assert TORSO["shared_expert_gated"] is False
    assert TORSO["router_scores"] == "sigmoid" and TORSO["use_expert_bias"]
    assert TORSO["rms_norm_eps"] == CONFIG["norm_eps"]
    assert TORSO["mamba_num_heads"] * TORSO["mamba_head_dim"] \
        == CONFIG["expand"] * 2048 == 4096  # expand is of Mamba's own 2048
    assert TORSO["tokens"] == CONFIG["model"]["obs_dim"] == 8192 \
        == 64 * TORSO["chunk_size"]
    assert 20 * (376 + 17) == 7860 <= 8192
    assert CONFIG["model"]["compute_dtype"] == "bfloat16"
    assert CONFIG["learner"]["k"] == 1
    assert CONFIG["learner"]["batch_size"] in (2, 4)
    # the rehearsal has all three kinds of block, at least three chunks of
    # the scan a sequence, the last short, and a strict share of the experts
    small = cellbuild.load_config("humanoid-nemotronh-ep16", True)["model"][
        "torso"]
    assert set(small["hybrid_override_pattern"]) == {"M", "E", "*"}
    assert small["tokens"] >= 3 * small["chunk_size"] \
        and small["tokens"] % small["chunk_size"]
    lo, hi = small["experts_held"]
    assert 0 < hi - lo < small["num_experts"]
    assert small["mlp_hidden_act"] == "relu2"
    assert small["routed_scaling_factor"] == 2.5


def test_the_cut_is_written_down_and_keeps_the_floors():
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held",
                                 "vocab", "lm_head", "denoiser_tower"]
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 52 and pub["n_routed_experts"] == 128
    assert pub["vocab_size"] == 131072 and "adaLN" in pub["denoiser_tower"]
    assert CONFIG["num_hidden_layers"] == 7 \
        == len(TORSO["hybrid_override_pattern"]) >= 4
    lo, hi = CONFIG["experts_held"]
    assert TORSO["experts_held"] == [lo, hi] and hi - lo == 8
    assert CONFIG["vocab"] == TORSO["vocab_rows"] == 131072 // 8
    assert TORSO["bins"] == 1024 and CONFIG["lm_head"] is False
    assert CONFIG["denoiser_tower"] is False
    assert "one chip of 16" in CONFIG["stands_for"]
    assert "45 blocks left out" in CONFIG["stands_for"]
    assert "16 chips" in CONFIG["reduced_why"]
    for text in (CONFIG["limits_why"], CONFIG["reduced_why"],
                 CONFIG["parameters_here"]["note"], *CONFIG["assumed"]):
        assert text and "PLACEHOLDER" not in text
    assert "reset128" in CONFIG["limits_why"] \
        and "fp8" in CONFIG["limits_why"]
    for marked in ("[z | xBC | dt]", "gate before the norm", "groups of 512",
                   "no rotary embedding", "A ~ U(1, 16)", "D ones",
                   "chunks of 128", "20 Humanoid-v4 steps", "relu(h U)^2",
                   "1e-6", "gamma", "rescale_prenorm_residual",
                   "1,536 tokens a pass", "1,920"):
        assert any(marked in line for line in CONFIG["assumed"]), marked
    assert CONFIG["seeded_decay"] == {"A": [1.0, 16.0], "dt": [1e-3, 0.1]}
    assert CONFIG["seeded_decay"]["dt"] == [CONFIG["time_step_min"],
                                            CONFIG["time_step_max"]]


def test_parameters_here_are_the_programs_own_tree():
    import jax

    from d4pg_tpu.learner import init_state

    config = cellbuild.learner_config(CONFIG)
    state = jax.eval_shape(lambda: init_state(config, jax.random.key(0)))
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa
    here = CONFIG["parameters_here"]
    torso = state.critic_params["params"]["torso"]
    assert size(torso) == here["torso"] == 484052928
    # ISSUE 45's arithmetic, leaf by leaf
    mam, moe, att = torso["layer_0"], torso["layer_1"], torso["layer_5"]
    assert {n: size(mam[n]) for n in mam} == {
        "mamba_norm": 2688, "in_proj": 2688 * 10304,
        "conv": 6144 * 4 + 6144, "A_log": 64, "dt_bias": 64, "D": 64,
        "out_norm": 4096, "out_proj": 4096 * 2688}
    assert {n: size(att[n]) for n in att} == {
        "attn_norm": 2688, "q": 2688 * 4096, "k": 2688 * 256,
        "v": 2688 * 256, "o": 4096 * 2688}
    assert {n: size(moe[n]) for n in moe} == {
        "moe_norm": 2688, "router": 2688 * 128 + 128,
        "up": 8 * 2688 * 1856, "down": 8 * 1856 * 2688,
        "shared_up": 2688 * 3712, "shared_down": 3712 * 2688}
    assert size(moe["up"]) + size(moe["down"]) == here["experts_a_block"] \
        == 8 * 9977856
    assert size(moe["shared_up"]) + size(moe["shared_down"]) \
        == here["shared_expert_a_block"]
    for i, kind in enumerate("MEMEM*E"):
        assert size(torso[f"layer_{i}"]) == {
            "M": here["mamba_block"], "E": here["expert_block"],
            "*": here["attention_block"]}[kind], i
    assert (here["mamba_block"], here["attention_block"],
            here["expert_block"]) == (38744896, 23399040, 100125440)
    assert size(torso["embed"]) == here["embedding"] == 16384 * 2688
    assert size(torso["final_norm"]) == here["final_norm"] == 2688
    assert size(state.critic_params) + size(state.actor_params) \
        == here["total"] == here["torso"] + here["heads"]
    assert 9.6e9 < 20 * here["torso"] < 9.7e9
    # over the floor of a quarter of the chip's 16.9 GB, under the chip
    assert 0.25 < 20 * here["total"] / 16.9e9 < 0.7
    # the ring the file states: 32,768 rows of two 8,192-wide fields
    row = 4 * (2 * 8192 + 17 + 3)
    assert 2.1e9 < row * CONFIG["replay"]["capacity"] < 2.2e9
    # layers 1-9, the longest unit, would leave too little: ISSUE 45's count
    nine = here["torso"] + here["mamba_block"] + here["expert_block"]
    assert nine == 622923264 and 12.4e9 < 20 * nine < 12.5e9


def test_the_file_holds_every_key_of_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if "Nemotron-Labs-TwoTower-30B-A3B" in line)
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key
    # no width among the cuts
    assert not [k for k in CONFIG["reduced"] if k.endswith(
        ("_size", "_dim", "_rank", "per_tok"))]


def test_seeded_leaves_are_put_right_by_the_driver():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import learner_static_ssm as driver

    cfg = cellbuild.load_config("humanoid-nemotronh-ep16", True)
    config = cellbuild.learner_config(cfg)
    make = jax.jit(lambda s: driver.seeded_params(cfg, config, s))
    actor, critic = make(jnp.uint32(12345))
    layers = critic["params"]["torso"]
    std = lambda x: float(jnp.std(x))  # noqa: E731
    mam, moe, att = layers["layer_0"], layers["layer_1"], layers["layer_3"]
    # every leaf at its own fan-in: the taps' is 4, an expert's its rows
    assert std(mam["conv"]["kernel"]) == pytest.approx(0.5, rel=0.25)
    assert std(mam["in_proj"]["kernel"]) == pytest.approx(32 ** -0.5, rel=0.1)
    assert std(moe["up"]["kernel"]) == pytest.approx(32 ** -0.5, rel=0.15)
    assert std(moe["shared_down"]["kernel"]) == pytest.approx(
        48 ** -0.5, rel=0.1)
    assert std(att["q"]["kernel"]) == pytest.approx(32 ** -0.5, rel=0.15)
    bias = np.asarray(mam["conv"]["bias"])
    assert 0.3 < np.abs(bias).max() <= 0.5 and abs(bias.mean()) < 0.15
    np.testing.assert_array_equal(np.asarray(mam["D"]["value"]), 1.0)
    # the decay: A in [1, 16), dt in [1e-3, 1e-1] behind a softplus
    a = np.exp(np.asarray(mam["A_log"]["value"], np.float64))
    dt = np.log1p(np.exp(np.asarray(mam["dt_bias"]["value"], np.float64)))
    assert a.shape == dt.shape == (4,)
    assert np.all((a >= 1) & (a < 16)) and len(set(a.tolist())) == 4
    assert np.all((dt > 0.99e-3) & (dt < 0.101))
    # the routing biases: whole multiples of gamma within 16 steps of zero
    steps = np.asarray(moe["router"]["bias"], np.float64) / 1e-3
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert np.abs(steps).max() <= 16 and len(set(np.round(steps))) > 2
    # the heads are left as they were: their biases zero
    for path, leaf in jax.tree_util.tree_flatten_with_path(actor)[0]:
        if "bias" in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    # another seed, another decay; the same seed, the same
    value = lambda t: np.asarray(t["layer_0"]["A_log"]["value"])  # noqa: E731
    other = make(jnp.uint32(54321))[1]["params"]["torso"]
    again = make(jnp.uint32(12345))[1]["params"]["torso"]
    assert not np.array_equal(value(other), value(layers))
    np.testing.assert_array_equal(value(again), value(layers))
    assert not np.array_equal(value(layers), np.asarray(
        layers["layer_2"]["A_log"]["value"]))
    assert driver.CELL is driver.SsmCell


def test_the_counts_are_the_references_own_products():
    """Every product the reference makes for one sequence of each kind of
    block, counted as it is made (``ops`` that count ``2 m k n``), is what
    ``shapes_ssm`` says of one forward pass; the recurrence, which the
    reference writes without a product routine, by brute force."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_ssm as rs

    cfg = cellbuild.load_config("humanoid-nemotronh-ep16", True)
    config = cellbuild.learner_config(cfg)
    t = cfg["model"]["torso"]
    t_len = t["tokens"]
    made = []

    def dot(x, w):
        made.append(2.0 * x.shape[0] * x.shape[1] * w.shape[1])
        return jnp.dot(x, w)

    from d4pg_tpu.learner import init_state

    layers = jax.eval_shape(lambda: init_state(
        config, jax.random.key(0))).critic_params["params"]["torso"]
    x = jax.ShapeDtypeStruct((t_len, t["hidden_size"]), jnp.float32)
    one_m = {**t, "hybrid_override_pattern": "M"}
    one_a = {**t, "hybrid_override_pattern": "*"}
    one_e = {**t, "hybrid_override_pattern": "E", "experts_held": [0, 1]}
    jax.eval_shape(lambda p, x: rs.block({"dot": dot}, t, p, x, "mamba"),
                   layers["layer_0"], x)
    assert sum(made) == pytest.approx(
        shapes_ssm.mamba_counts(one_m, 1)["flops"] / 5)
    # the attention block's products sit inside the reference's loops over
    # heads and blocks of queries, which a trace sees once: by brute force,
    # a loop over every position, head and kept pair
    d, heads, dh = t["hidden_size"], t["num_attention_heads"], t["head_dim"]
    kv = t["num_key_value_heads"] * dh
    flops = 2 * t_len * (2 * d * heads * dh + 2 * d * kv)
    for pos in range(t_len):
        for _s in range(pos + 1):
            flops += 2 * heads * dh * 2  # one score and one weighted value
    assert shapes_ssm.attention_counts(one_a, 1)["flops"] \
        == pytest.approx(5 * flops)
    # one expert and every token assigned to it: two matrices a row
    made.clear()
    h = jax.ShapeDtypeStruct((t_len, t["hidden_size"]), jnp.float32)
    jax.eval_shape(lambda h, up, down: rs.relu2(
        {"dot": dot}, h, up, down), h, *(jax.ShapeDtypeStruct(
            s.shape[1:], jnp.float32) for s in (
                layers["layer_1"]["up"]["kernel"],
                layers["layer_1"]["down"]["kernel"])))
    assert sum(made) == pytest.approx(
        shapes_ssm.expert_counts(one_e, t_len)["flops"] / 5)
    # the shared expert and the router of one E block
    made.clear()
    jax.eval_shape(lambda h, p: rs.relu2(
        {"dot": dot}, h, p["shared_up"]["kernel"],
        p["shared_down"]["kernel"]), h, layers["layer_1"])
    router = 2.0 * t_len * t["hidden_size"] * t["num_experts"]
    assert sum(made) + router == pytest.approx(
        shapes_ssm.alike_flops(one_e, 1) / 5)
    # the recurrence by brute force: a decay, a write and a read of a
    # [P, N] state a head and token
    p, n = t["mamba_head_dim"], t["ssm_state_size"]
    flops = 0
    for _t in range(t_len):
        for _h in range(t["mamba_num_heads"]):
            flops += p * n + 2 * p * n + 2 * p * n
    got = shapes_ssm.ssd_scan_counts(one_m, 1)
    assert got["flops"] == pytest.approx(5 * flops)
    width = 2 * t["mamba_num_heads"] * p + 2 * t["n_groups"] * n \
        + t["mamba_num_heads"]
    assert got["bytes"] == pytest.approx(5 * 4 * t_len * width)


def test_the_counts_at_the_cells_size_are_issue_45s():
    batch, t_len = 4, 8192
    tokens = batch * t_len
    got = shapes_ssm.mamba_counts(TORSO, batch)
    assert got["flops"] == pytest.approx(
        5 * 3 * tokens * 2 * (2688 * 10304 + 4096 * 2688))
    assert got["bytes"] == pytest.approx(
        5 * 3 * tokens * 2 * 2 * (4096 + 6144 + 4096))
    scan = shapes_ssm.ssd_scan_counts(TORSO, batch)
    assert scan["flops"] == pytest.approx(
        5 * 3 * tokens * 5 * 64 * 128 * 64)
    assert scan["bytes"] == pytest.approx(
        5 * 3 * tokens * 4 * (2 * 4096 + 2 * 1024 + 64))
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12  # bound by bytes
    attn = shapes_ssm.attention_counts(TORSO, batch)
    assert attn["flops"] == pytest.approx(5 * batch * 2 * (
        t_len * (2 * 2688 * 4096 + 2 * 2688 * 256)
        + shapes_ssm.causal_pairs(t_len) * 32 * 128 * 2))
    # an even load is 1,536 assignments an expert, a block and a pass
    counts = np.full((1, 3, 128), tokens * 6 // 128, np.int64)
    assert counts[0, 0, 0] == 1536
    rows = shapes_ssm.held_assignments(TORSO, counts)
    assert rows == 3 * 8 * 1536
    assert shapes_ssm.expert_counts(TORSO, rows)["flops"] == pytest.approx(
        5 * 2 * rows * 2 * 2688 * 1856)
    assert shapes_ssm.load_max_over_mean(TORSO, counts) == 1.0
    # ISSUE 45: ~498 MFLOP a token forward, 81.6 TFLOP a step; Mamba 48 %,
    # the E blocks 29 %, attention 23 %
    total = shapes_ssm.step_flops(TORSO, batch, counts)
    assert 81.0e12 < total < 82.2e12
    assert total / (5 * tokens) == pytest.approx(498e6, rel=0.01)
    mamba = shapes_ssm.mamba_counts(TORSO, batch)["flops"] + scan["flops"]
    assert mamba / total == pytest.approx(0.48, abs=0.01)
    assert attn["flops"] / total == pytest.approx(0.23, abs=0.01)
    assert shapes_ssm.kept_share([[0.8, 0.9, 0.7]]) == pytest.approx(80.0)
    assert shapes_ssm.swapped_share(
        TORSO, [[9830.4, 9830.4, 9830.4]], batch) == pytest.approx(5.0)


def test_the_cell_is_one_chip_and_lists_its_fifteen_layer_metrics():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "learn-static-ssm"
    assert "PLACEHOLDER" not in cell["why"] and len(cell["why"]) <= 200
    traced = manifest.metrics_for(man, CELL, True)
    assert set(traced) == {"compile_s", *METRICS}
    assert set(manifest.metrics_for(man, CELL, False)) == {
        "grad_steps_per_s", "setup_s"}
    # the other torso cells' readers are not asked in this cell, nor this
    # cell's in theirs
    for other in ("humanoid-mellum2-ep4.learn-static",
                  "humanoid-keye2-ep8.learn-static",
                  "humanoid-lfm2-ep4.learn-static",
                  "humanoid-qwen3next-ep32.learn-static",
                  "humanoid-ouro-ut4.learn-static"):
        theirs = manifest.metrics_for(man, other, True)
        assert set(theirs) & set(traced) == {"compile_s"}
    for entry in traced.values():
        if entry["name"] != "compile_s":
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "grad_steps_per_s"
            if entry["name"].endswith(("_roofline", "_mfu")):
                assert entry["unit"] == "%" and entry["better"] == "higher"
    assert traced["ssm_step_mfu"]["layer"] == "fused chunk"
    assert traced["ssd_kept_share"]["source"] == "program_counter"
    # the entries this cell brought stand together and in order, from the
    # first of them on: whatever a later PR appends comes behind them
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(FIRST_METRIC)
    assert names[at:at + len(METRICS)] == METRICS
    assert names[at - 1] == "exit_entropy_share"
    cells = [w["name"] for w in man["workloads"]]
    assert len(cells) >= 9 and cells.index(CELL) == cells.index(
        "humanoid-ouro-ut4.learn-static") + 1
    configs = [c["name"] for c in man["configs"]]
    at = configs.index("humanoid-nemotronh-ep16")
    assert at == configs.index("humanoid-ouro-ut4") + 1
    assert man["configs"][at]["reduced"] == CONFIG["reduced"]
    assert man["configs"][at]["source"] == CONFIG["source"]
    # the manifest's own limit on a line of text: 1 to 200 characters
    for text in (man["configs"][at]["why"], man["configs"][at]["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_the_readers_read_this_cell_and_no_other():
    """On a context that is another cell's every reader of this cell returns
    nothing, and the other cells' roofline readers return nothing on this
    cell's."""
    from benchmark import linear_trace, loop_trace, run, ssm_trace

    log = lambda _m: None  # noqa: E731
    for key in ("torso", "sparse", "hybrid", "linear", "loop"):
        theirs = {"log": log, "trace": object(), key: TORSO, "k": 1,
                  "chunk_text": "", "chunk_program": "jit_fn",
                  "batch_size": 4, "route_counts": np.ones((1, 3, 128)),
                  "ssd_kept": np.ones((1, 3)),
                  "bias_swapped": np.ones((1, 3))}
        theirs.update({f"{k}_trace": None for k in (
            "torso", "sparse", "hybrid", "linear", "loop")})
        for name in METRICS:
            assert run.layer_reader(name)(dict(theirs)) is None, name
    mine = {"log": log, "trace": object(), "ssm": TORSO, "k": 1,
            "ssm_trace": None, "batch_size": 4}
    assert linear_trace.delta_scan_roofline(dict(mine)) is None
    assert loop_trace.step_mfu(dict(mine)) is None
    assert ssm_trace.ssd_scan_roofline(dict(mine)) is None  # no trace
    assert ssm_trace.step_mfu(dict(mine)) is None
    kept = np.asarray([[0.85, 0.86, 0.82]])
    assert ssm_trace.kept_share({**mine, "ssd_kept": kept}) \
        == pytest.approx(84.333, abs=1e-2)
    counts = np.full((1, 3, 128), 1536)
    assert ssm_trace.load_max_over_mean(
        {**mine, "route_counts": counts}) == 1.0
    assert ssm_trace.swapped_share(
        {**mine, "bias_swapped": np.full((1, 3), 1966.08)}) \
        == pytest.approx(1.0)
    # a program without the counters (the parent's) gives the readers nothing
    for reader, name in ((ssm_trace.kept_share, "ssd_kept"),
                         (ssm_trace.swapped_share, "bias_swapped"),
                         (ssm_trace.load_max_over_mean, "route_counts")):
        assert reader({**mine, name: None}) is None
        assert reader(dict(mine)) is None


def test_the_scopes_are_read_from_a_chunk_programs_text():
    """``ssm_trace`` on a hand-made analysis: each scope's time goes to its
    own metric, a roofline is the least time over the time spent, the whole
    step's share is the needed FLOPs at peak over the chunk's time."""
    from benchmark import program_trace, ssm_trace

    assert {"torso.mamba", "torso.ssd_scan", "torso.attn_full",
            "torso.shared_expert", "torso.route", "torso.experts"} \
        <= set(ssm_trace.SSM_SCOPES)
    assert set(program_trace.TOP_SCOPES) <= set(ssm_trace.ALL_SCOPES)
    found = {"total": 2.0, "covered": 1.0, "step": {
        s: 0.0 for s in ssm_trace.ALL_SCOPES}}
    found["step"].update({"torso.ssd_scan": 0.3, "torso.mamba": 0.8,
                          "torso.attn_full": 0.4})
    counts = np.full((1, 3, 128), 1536)
    ctx = {"log": lambda _m: None, "ssm": TORSO, "ssm_trace": found,
           "batch_size": 4, "trace": object(), "k": 1,
           "route_counts": counts,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert ssm_trace.scope_us(ctx, "torso.ssd_scan") == pytest.approx(0.3e6)
    assert ssm_trace.chunk_ms(ctx) == 2000.0
    scan = shapes_ssm.ssd_scan_counts(TORSO, 4)
    assert ssm_trace.ssd_scan_roofline(ctx) == pytest.approx(
        100 * scan["bytes"] / 819e9 / 0.3)
    flops = shapes_ssm.mamba_counts(TORSO, 4)["flops"]
    assert ssm_trace.mamba_roofline(ctx) == pytest.approx(
        100 * flops / 197e12 / 0.8)
    flops = shapes_ssm.attention_counts(TORSO, 4)["flops"]
    assert ssm_trace.attention_roofline(ctx) == pytest.approx(
        100 * flops / 197e12 / 0.4)
    assert ssm_trace.step_mfu(ctx) == pytest.approx(
        100 * shapes_ssm.step_flops(TORSO, 4, counts) / 197e12 / 2.0)
    assert 15 < ssm_trace.step_mfu(ctx) < 25
    # a scope no operation carries reads 0.0, not a division by zero
    assert ssm_trace.experts_roofline(ctx) == 0.0


@pytest.fixture(scope="module")
def rehearsed():
    """The cell at rehearsal size, its first chunk run and its program
    given up: what ``benchmark/tools/calibrate_controls.py`` does a seed."""
    import time

    from benchmark.drivers import learner_static_ssm as driver
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
    to zero at every chunk's edge, each break at least one of them."""
    from benchmark.learner import judge
    from benchmark.tools.calibrate_controls import exceeded

    limits = rehearsed.env.cfg["limits"]
    quiet = lambda _m: None  # noqa: E731
    sound = rehearsed.check_first_chunk()
    assert {"ssd_kept_gap", "bias_gap", "route_hist_gap", "td_gap"} \
        <= set(sound)
    assert judge(sound, limits, quiet), exceeded(sound, limits)
    controls = rehearsed.control_numbers()
    assert set(controls) == {"fp8", "reset128"}
    for name, numbers in controls.items():
        assert exceeded(numbers, limits), (name, numbers)
        assert not judge(numbers, limits, quiet), name
    # a scan without memory moves what follows it, the next blocks' routing
    # first; the counter it is read by (dt and A come before the scan) moves
    # only through the blocks behind the first
    reset = controls["reset128"]
    assert reset["route_hist_gap"] > limits["route_hist_gap"]
    assert reset["ssd_kept_gap"] < limits["ssd_kept_gap"]
