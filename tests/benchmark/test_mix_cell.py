"""The Trinity-Mini torso cell (``humanoid-trinity-ep16.learn-static``): its
configuration file against the published config, the catalog and the program's
own parameter tree, its driver's seeded weights, the three controls at
rehearsal size, the operation counts its rooflines use against the reference's
own products and hand counts, and what the manifest lists for it (its sound
rehearsals in both trace modes are here too, through ``rehearsal.py``; the
files found by name are ``test_manifest_files.py``'s).
"""

import json
import math
import os

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes_mix
from rehearsal import assert_result_line, rehearse

CELL = "humanoid-trinity-ep16.learn-static"
CONFIG = cellbuild.load_config("humanoid-trinity-ep16", False)
TORSO = CONFIG["model"]["torso"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
FIRST_METRIC = "mix_chunk_device_ms"  # the first entry this cell brought
METRICS = [
    "mix_chunk_device_ms", "mix_attn_window_us_per_step",
    "mix_attn_full_us_per_step", "mix_attn_window_roofline",
    "mix_attn_full_roofline", "mix_dense_mlp_us_per_step",
    "mix_route_us_per_step", "mix_experts_us_per_step",
    "mix_shared_expert_us_per_step", "mix_experts_roofline", "mix_step_mfu",
    "mix_expert_load_max_over_mean", "mix_bias_swapped_share"]
SLIDING, FULL = "sliding_attention", "full_attention"

# the published widths, written out: the file may not drift from them
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "sliding_window": 2048, "num_experts": 128,
    "num_experts_per_tok": 8, "moe_intermediate_size": 1024,
    "num_shared_experts": 1, "intermediate_size": 6144, "route_scale": 2.826,
    "route_norm": True, "score_func": "sigmoid", "load_balance_coeff": 0.001,
    "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "global_attn_every_n_layers": 4,
    "hidden_act": "silu", "mup_enabled": True, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "rope_scaling": None, "vocab_size": 200192,
    "max_position_embeddings": 131072, "model_type": "afmoe",
    "tie_word_embeddings": False,
}


@pytest.mark.parametrize("key, value", sorted(PUBLISHED.items()))
def test_every_width_is_as_published(key, value):
    assert CONFIG[key] == value
    if key in TORSO:
        assert TORSO[key] == value


def test_the_layers_reach_the_program_as_published():
    # published layers 1-5 of 32: S | S F S S, full where (l + 1) % 4 == 0
    pattern = CONFIG["layer_types"]
    assert len(pattern) == 32 == CONFIG["published"]["num_hidden_layers"]
    assert pattern == [FULL if (i + 1) % 4 == 0 else SLIDING
                       for i in range(32)]
    assert TORSO["layer_types"] == pattern[1:6] == [
        SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    assert TORSO["name"] == "trinity"
    assert TORSO["num_dense_layers"] == CONFIG["num_dense_layers"] == 1
    assert CONFIG["published"]["num_dense_layers"] == 2
    # every mechanism is a flag some older cell sets; here they meet
    assert TORSO["qk_norm"] and TORSO["attn_output_gate"] \
        and TORSO["sandwich_norm"] and TORSO["use_expert_bias"]
    assert TORSO["router_scores"] == CONFIG["score_func"] == "sigmoid"
    assert TORSO["routed_scaling_factor"] == CONFIG["route_scale"] == 2.826
    assert TORSO["bias_update_rate"] == CONFIG["load_balance_coeff"] == 1e-3
    assert TORSO["norm_topk_prob"] is CONFIG["route_norm"] is True
    assert TORSO["shared_expert_intermediate_size"] \
        == CONFIG["num_shared_experts"] * CONFIG["moe_intermediate_size"]
    assert TORSO["shared_expert_gated"] is False
    assert TORSO["mlp_hidden_act"] == CONFIG["hidden_act"] == "silu"
    assert TORSO["embedding_multiplier"] == math.sqrt(2048)
    # two rotary regimes, one of them none, each named
    assert TORSO["rope_parameters"] == {
        SLIDING: {"rope_type": "default", "rope_theta": 10000}, FULL: None}
    assert TORSO["tokens"] == CONFIG["model"]["obs_dim"] == 16384 \
        == 8 * TORSO["sliding_window"]
    assert 41 * (376 + 17) == 16113 <= 16384
    assert CONFIG["model"]["compute_dtype"] == "bfloat16"
    assert CONFIG["learner"]["k"] == 1
    assert CONFIG["learner"]["batch_size"] == 2
    # heads, optimiser, PER, tau as humanoid-keye2-ep8
    keye = cellbuild.load_config("humanoid-keye2-ep8", False)
    for block in ("learner", "replay", "data"):
        assert CONFIG[block] == keye[block], block
    for key in ("obs_dim", "act_dim", "hidden", "n_atoms", "v_min", "v_max",
                "compute_dtype", "projection", "lr_actor", "lr_critic",
                "tau"):
        assert CONFIG["model"][key] == keye["model"][key], key
    # the rehearsal: [sliding dense, sliding moe, full moe], a window that
    # cuts, a strict share of the experts, the published scale
    small = cellbuild.load_config("humanoid-trinity-ep16", True)["model"][
        "torso"]
    assert small["layer_types"] == [SLIDING, SLIDING, FULL]
    assert small["num_dense_layers"] == 1
    assert small["sliding_window"] < small["tokens"] <= 256
    lo, hi = small["experts_held"]
    assert 0 < hi - lo < small["num_experts"]
    assert small["routed_scaling_factor"] == 2.826
    assert small["embedding_multiplier"] == math.sqrt(small["hidden_size"])
    assert small["rope_parameters"][FULL] is None


def test_the_cut_is_written_down_and_keeps_the_floors():
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                 "experts_held", "vocab", "lm_head"]
    pub = CONFIG["published"]
    assert pub["num_hidden_layers"] == 32 and pub["num_experts"] == 128
    assert pub["vocab_size"] == 200192 and "S | S F S S" in pub["layer_types"]
    assert CONFIG["num_hidden_layers"] == 5 == len(TORSO["layer_types"]) >= 4
    lo, hi = CONFIG["experts_held"]
    assert TORSO["experts_held"] == [lo, hi] and hi - lo == 8
    assert CONFIG["vocab"] == TORSO["vocab_rows"] == 200192 // 8 == 25024
    assert TORSO["bins"] == 1024 and CONFIG["lm_head"] is False
    assert "one chip of 16" in CONFIG["stands_for"]
    assert "41-step history" in CONFIG["stands_for"]
    assert "13.7 GB" in CONFIG["reduced_why"]
    for text in (CONFIG["limits_why"], CONFIG["reduced_why"],
                 CONFIG["stands_for"], CONFIG["parameters_here"]["note"],
                 *CONFIG["assumed"]):
        assert text and "PLACEHOLDER" not in text
    for control in ("fp8", "all_full", "roped_full"):
        assert control in CONFIG["limits_why"], control
    # ISSUE 49's list of what the catalog's config has no key for, by name
    for marked in ("four norms' wiring", "q/k norm before the rotation",
                   "normed input, applied before o",
                   "no rotary embedding on full_attention",
                   "scores without the bias", "1e-20", "1e-6",
                   "on an expert's OUTPUT",
                   "num_shared_experts x moe_intermediate_size",
                   "DeepSeek-V3's sign rule", "RATE is published",
                   "counts the query's own position",
                   "as humanoid-lfm2-ep4", "rope_scaling null",
                   "no auxiliary loss", "permutation of the columns",
                   "41 Humanoid-v4 steps", "2,048 tokens a pass",
                   "one layer in five"):
        assert any(marked in line for line in CONFIG["assumed"]), marked


def test_parameters_here_are_the_programs_own_tree():
    import jax

    from d4pg_tpu.learner import init_state

    config = cellbuild.learner_config(CONFIG)
    state = jax.eval_shape(lambda: init_state(config, jax.random.key(0)))
    size = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))  # noqa
    here = CONFIG["parameters_here"]
    torso = state.critic_params["params"]["torso"]
    assert size(torso) == here["torso"] == 452898560
    # ISSUE 49's arithmetic, leaf by leaf
    dense, moe = torso["layer_0"], torso["layer_1"]
    attention = {"attn_norm": 2048, "q": 2048 * 8192, "k": 2048 * 512,
                 "v": 2048 * 512, "o": 4096 * 2048, "q_norm": 128,
                 "k_norm": 128, "op_post_norm": 2048, "ff_post_norm": 2048}
    assert {n: size(dense[n]) for n in dense} == {
        **attention, "mlp_norm": 2048, "w1": 2048 * 6144, "w3": 2048 * 6144,
        "w2": 6144 * 2048}
    assert {n: size(moe[n]) for n in moe} == {
        **attention, "moe_norm": 2048, "router": 2048 * 128 + 128,
        "gate": 8 * 2048 * 1024, "up": 8 * 2048 * 1024,
        "down": 8 * 1024 * 2048, "shared_gate": 2048 * 1024,
        "shared_up": 2048 * 1024, "shared_down": 1024 * 2048}
    assert sum(attention[n] for n in ("q", "k", "v", "o", "q_norm",
                                      "k_norm")) \
        == here["attention_a_layer"] == 27263232
    assert here["norms_a_layer"] == 4 * 2048
    assert size(dense) == here["dense_layer"] == 65020160 \
        == here["attention_a_layer"] + here["norms_a_layer"] \
        + here["dense_mlp"]
    assert sum(size(moe[n]) for n in ("gate", "up", "down")) \
        == here["experts_a_layer"] == 8 * 6291456
    assert sum(size(moe[n]) for n in ("shared_gate", "shared_up",
                                      "shared_down")) \
        == here["shared_expert_a_layer"] == 6291456
    assert here["router_a_layer"] + here["bias_a_layer"] \
        == size(moe["router"])
    for i in range(1, 5):
        assert size(torso[f"layer_{i}"]) == here["expert_layer"] == 84156800
    assert size(torso["embed"]) == here["embedding"] == 25024 * 2048
    assert size(torso["final_norm"]) == here["final_norm"] == 2048
    assert size(state.critic_params) + size(state.actor_params) \
        == here["total"] == here["torso"] + here["heads"]
    assert 9.0e9 < 20 * here["torso"] < 9.1e9
    # over the floor of a quarter of the chip's 16.9 GB, under the chip
    assert 0.25 < 20 * here["total"] / 16.9e9 < 0.7
    # the ring the file states: 16,384 rows of two 16,384-wide fields
    row = 4 * (2 * 16384 + 17 + 3)
    assert 2.1e9 < row * CONFIG["replay"]["capacity"] < 2.2e9
    # layers 0-7 (both dense layers, two periods) would leave too little,
    # and 16 held experts a layer too: ISSUE 49's counts
    eight = here["torso"] + here["dense_layer"] + 2 * here["expert_layer"]
    assert 13.6e9 < 20 * eight < 13.8e9
    sixteen = here["torso"] + 4 * here["experts_a_layer"]
    assert 13.0e9 < 20 * sixteen < 13.2e9


def test_the_file_holds_every_key_of_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Trinity-Mini"' in line)
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

    from benchmark.drivers import learner_static_mix as driver

    cfg = cellbuild.load_config("humanoid-trinity-ep16", True)
    config = cellbuild.learner_config(cfg)
    make = jax.jit(lambda s: driver.seeded_params(cfg, config, s))
    actor, critic = make(jnp.uint32(12345))
    layers = critic["params"]["torso"]
    std = lambda x: float(jnp.std(x))  # noqa: E731
    dense, moe = layers["layer_0"], layers["layer_1"]
    # every leaf at its own fan-in: an expert's its rows, the query and gate
    # columns of the one q leaf alike; embedding rows N(0, 1)
    assert std(dense["q"]["kernel"]) == pytest.approx(64 ** -0.5, rel=0.1)
    assert std(dense["w2"]["kernel"]) == pytest.approx(96 ** -0.5, rel=0.1)
    assert std(moe["up"]["kernel"]) == pytest.approx(64 ** -0.5, rel=0.15)
    assert std(moe["down"]["kernel"]) == pytest.approx(32 ** -0.5, rel=0.15)
    assert std(moe["shared_down"]["kernel"]) == pytest.approx(
        32 ** -0.5, rel=0.15)
    assert std(layers["embed"]["kernel"]) == pytest.approx(1.0, rel=0.1)
    for name in ("attn_norm", "op_post_norm", "moe_norm", "ff_post_norm",
                 "q_norm", "k_norm"):
        np.testing.assert_array_equal(np.asarray(moe[name]["scale"]), 1.0)
    # the routing biases: whole multiples of the rate within 16 steps of zero
    steps = np.asarray(moe["router"]["bias"], np.float64) / 1e-3
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    assert np.abs(steps).max() <= 16 and len(set(np.round(steps))) > 2
    assert not np.array_equal(np.asarray(moe["router"]["bias"]), np.asarray(
        layers["layer_2"]["router"]["bias"]))
    # the heads are left as they were: their biases zero
    for path, leaf in jax.tree_util.tree_flatten_with_path(actor)[0]:
        if "bias" in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    assert driver.CELL is driver.MixCell


def test_the_counts_are_the_references_own_products():
    """Every product the reference makes for one sequence of each kind of
    layer, counted as it is made (``ops`` that count ``2 m k n``), is what
    ``shapes_mix`` says of one forward pass; the pairs, which the reference
    scores under a mask, by brute force from the mask itself."""
    import jax
    import jax.numpy as jnp

    from benchmark import reference_mix as rm
    from d4pg_tpu.learner import init_state

    cfg = cellbuild.load_config("humanoid-trinity-ep16", True)
    config = cellbuild.learner_config(cfg)
    t = cfg["model"]["torso"]
    t_len, d = t["tokens"], t["hidden_size"]
    heads, dh = t["num_attention_heads"], t["head_dim"]
    hkv = t["num_key_value_heads"]
    made = []

    def dot(x, w):
        made.append(2.0 * x.shape[0] * x.shape[1] * w.shape[1])
        return jnp.dot(x, w)

    layers = jax.eval_shape(lambda: init_state(
        config, jax.random.key(0))).critic_params["params"]["torso"]
    h = jax.ShapeDtypeStruct((t_len, d), jnp.float32)
    f32 = lambda leaf, cut=0: jax.ShapeDtypeStruct(  # noqa: E731
        leaf["kernel"].shape[cut:], jnp.float32)
    # the five projections: the reference makes them a key/value head at a
    # time inside a scan, which a trace sees once
    for kind in shapes_mix.KINDS:
        made.clear()
        jax.eval_shape(lambda p, h, kind=kind: rm.attention_op(
            {"dot": dot, "einsum": rm.EXACT_OPS["einsum"]}, t, p, h, kind),
            layers["layer_1"], h)
        window = t["sliding_window"] if kind == SLIDING else None
        pairs = int(np.asarray(rm.visible(t_len, 0, t_len, window)).sum())
        assert pairs == shapes_mix.kept_pairs(t_len, window)
        one = {**t, "layer_types": [kind]}
        assert hkv * sum(made) + 2.0 * pairs * heads * dh * 2 \
            == pytest.approx(
                shapes_mix.attention_counts(one, 1, kind)["flops"] / 5)
    assert shapes_mix.kept_pairs(t_len, t["sliding_window"]) \
        < shapes_mix.kept_pairs(t_len, None) == t_len * (t_len + 1) // 2
    # the dense layer's SwiGLU
    made.clear()
    p0 = layers["layer_0"]
    jax.eval_shape(lambda h, a, b, c: rm.swiglu({"dot": dot}, h, a, b, c), h,
                   f32(p0["w1"]), f32(p0["w3"]), f32(p0["w2"]))
    assert sum(made) == pytest.approx(shapes_mix.dense_flops(t, 1) / 5)
    # one expert and every token assigned to it: three matrices a row
    made.clear()
    p1 = layers["layer_1"]
    jax.eval_shape(lambda h, a, b, c: rm.swiglu({"dot": dot}, h, a, b, c), h,
                   f32(p1["gate"], 1), f32(p1["up"], 1), f32(p1["down"], 1))
    one = {**t, "layer_types": [SLIDING, FULL], "experts_held": [0, 1]}
    assert sum(made) == pytest.approx(
        shapes_mix.expert_counts(one, t_len)["flops"] / 5)
    # the shared expert and the router of one expert layer
    made.clear()
    jax.eval_shape(lambda h, a, b, c: rm.swiglu({"dot": dot}, h, a, b, c), h,
                   f32(p1["shared_gate"]), f32(p1["shared_up"]),
                   f32(p1["shared_down"]))
    router = 2.0 * t_len * d * t["num_experts"]
    assert sum(made) + router == pytest.approx(
        shapes_mix.alike_flops(one, 1) / 5)


def test_the_counts_at_the_cells_size_are_issue_49s():
    batch, t_len = 2, 16384
    tokens = batch * t_len
    # the window's pairs as the mask keeps them: min(t + 1, 2048) keys for
    # the t-th query; 0.235 of a full layer's
    window = shapes_mix.kept_pairs(t_len, 2048)
    full = shapes_mix.kept_pairs(t_len, None)
    assert window == 2048 * 2049 // 2 + (t_len - 2048) * 2048 == 31458304
    assert full == t_len * (t_len + 1) // 2 == 134225920
    assert window / full == pytest.approx(0.235, abs=0.001)
    proj = 2048 * (3 * 4096 + 2 * 512)  # q, gate, o and k, v
    sliding = shapes_mix.attention_counts(TORSO, batch, SLIDING)
    assert sliding["flops"] == pytest.approx(
        5 * 4 * 2 * (tokens * proj + batch * window * 32 * 128 * 2))
    whole = shapes_mix.attention_counts(TORSO, batch, FULL)
    assert whole["flops"] == pytest.approx(
        5 * 1 * 2 * (tokens * proj + batch * full * 32 * 128 * 2))
    # ISSUE 49: a forward pass a sequence 5.2e11 a window layer's pairs,
    # 2.2e12 the full layer's
    assert 2 * window * 32 * 128 * 2 == pytest.approx(5.2e11, rel=0.02)
    assert 2 * full * 32 * 128 * 2 == pytest.approx(2.2e12, rel=0.01)
    assert shapes_mix.dense_flops(TORSO, batch) == pytest.approx(
        5 * 2 * tokens * 3 * 2048 * 6144)
    # an even load is 2,048 assignments an expert, a layer and a pass
    counts = np.full((1, 4, 128), tokens * 8 // 128, np.int64)
    assert counts[0, 0, 0] == 2048
    rows = shapes_mix.held_assignments(TORSO, counts)
    assert rows == 4 * 8 * 2048
    assert shapes_mix.expert_counts(TORSO, rows)["flops"] == pytest.approx(
        5 * 2 * rows * 3 * 2048 * 1024)
    assert shapes_mix.alike_flops(TORSO, batch) == pytest.approx(
        5 * 4 * 2 * tokens * (3 * 2048 * 1024 + 2048 * 128))
    assert shapes_mix.load_max_over_mean(TORSO, counts) == 1.0
    # ISSUE 49: 1.13e13 FLOP a forward pass a sequence, attention's pairs
    # nearly two fifths of it; about 1.13e14 a step
    total = shapes_mix.step_flops(TORSO, batch, counts)
    assert total / (5 * batch) == pytest.approx(1.13e13, rel=0.02)
    pairs = 5 * batch * 2 * (4 * window + full) * 32 * 128 * 2
    assert pairs / total == pytest.approx(0.38, abs=0.02)
    assert shapes_mix.swapped_share(
        TORSO, [[13107.2] * 4], batch) == pytest.approx(5.0)


def test_the_cell_is_one_chip_and_lists_its_thirteen_layer_metrics():
    man = manifest.load()
    cell = manifest.cell(man, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "learn-static-mix"
    assert "PLACEHOLDER" not in cell["why"] and len(cell["why"]) <= 200
    assert "2 seqs x 16,384" in cell["why"] and "1/16" in cell["why"]
    assert "1 layer in 5" in cell["why"]
    traced = manifest.metrics_for(man, CELL, True)
    assert set(traced) == {"compile_s", *METRICS}
    assert set(manifest.metrics_for(man, CELL, False)) == {
        "grad_steps_per_s", "setup_s"}
    # the other torso cells' readers are not asked in this cell, nor this
    # cell's in theirs
    for other in man["workloads"]:
        if other["name"] != CELL:
            theirs = manifest.metrics_for(man, other["name"], True)
            assert set(theirs) & set(traced) == {"compile_s"}
    for entry in traced.values():
        if entry["name"] != "compile_s":
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "grad_steps_per_s"
            if entry["name"].endswith(("_roofline", "_mfu")):
                assert entry["unit"] == "%" and entry["better"] == "higher"
    assert traced["mix_step_mfu"]["layer"] == "fused chunk"
    assert traced["mix_bias_swapped_share"]["source"] == "program_counter"
    # the entries this cell brought stand together and in order, from the
    # first of them on: whatever a later PR appends comes behind them
    names = [m["name"] for m in man["per_layer"]]
    at = names.index(FIRST_METRIC)
    assert names[at:at + len(METRICS)] == METRICS
    assert names[at - 1] == "ssm_expert_load_max_over_mean"
    cells = [w["name"] for w in man["workloads"]]
    assert len(cells) >= 10 and cells.index(CELL) == cells.index(
        "humanoid-nemotronh-ep16.learn-static") + 1
    configs = [c["name"] for c in man["configs"]]
    at = configs.index("humanoid-trinity-ep16")
    assert at == configs.index("humanoid-nemotronh-ep16") + 1
    assert man["configs"][at]["reduced"] == CONFIG["reduced"]
    assert man["configs"][at]["source"] == CONFIG["source"]
    # the manifest's own limit on a line of text: 1 to 200 characters
    for text in (man["configs"][at]["why"], man["configs"][at]["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text


def test_the_readers_read_this_cell_and_no_other():
    """On a context that is another cell's every reader of this cell returns
    nothing, and the other cells' readers return nothing on this cell's."""
    from benchmark import mix_trace, run, ssm_trace, torso_trace

    log = lambda _m: None  # noqa: E731
    keys = ("torso", "sparse", "hybrid", "linear", "loop", "ssm")
    for key in keys:
        theirs = {"log": log, "trace": object(), key: TORSO, "k": 1,
                  "chunk_text": "", "chunk_program": "jit_fn",
                  "batch_size": 2, "route_counts": np.ones((1, 4, 128)),
                  "bias_swapped": np.ones((1, 4))}
        theirs.update({f"{k}_trace": None for k in keys})
        for name in METRICS:
            assert run.layer_reader(name)(dict(theirs)) is None, name
    mine = {"log": log, "trace": object(), "mix": TORSO, "k": 1,
            "mix_trace": None, "batch_size": 2}
    assert ssm_trace.step_mfu(dict(mine)) is None
    assert ssm_trace.attention_roofline(dict(mine)) is None
    assert torso_trace.scope_us({**mine, "torso_trace": None},
                                "torso.attn_window") is None
    for kind in shapes_mix.KINDS:  # no trace
        assert mix_trace.attention_roofline(dict(mine), kind) is None
    assert mix_trace.step_mfu(dict(mine)) is None
    counts = np.full((1, 4, 128), 2048)
    assert mix_trace.load_max_over_mean(
        {**mine, "route_counts": counts}) == 1.0
    assert mix_trace.swapped_share(
        {**mine, "bias_swapped": np.full((1, 4), 2621.44)}) \
        == pytest.approx(1.0)
    # a program without the counters (the parent's) gives the readers nothing
    for reader, name in ((mix_trace.swapped_share, "bias_swapped"),
                         (mix_trace.load_max_over_mean, "route_counts")):
        assert reader({**mine, name: None}) is None
        assert reader(dict(mine)) is None


def test_the_scopes_are_read_from_a_chunk_programs_text():
    """``mix_trace`` on a hand-made analysis: each scope's time goes to its
    own metric, a roofline is the least time over the time spent, apart for
    the two masks, the whole step's share is the needed FLOPs at peak over the
    chunk's time."""
    from benchmark import mix_trace, program_trace

    assert {"torso.attn_window", "torso.attn_full", "torso.mlp",
            "torso.shared_expert", "torso.route", "torso.experts",
            "torso.embed", "torso.pool"} == set(mix_trace.MIX_SCOPES)
    assert set(program_trace.TOP_SCOPES) <= set(mix_trace.ALL_SCOPES)
    found = {"total": 2.0, "covered": 1.0, "step": {
        s: 0.0 for s in mix_trace.ALL_SCOPES}}
    found["step"].update({"torso.attn_window": 0.8, "torso.attn_full": 0.4,
                          "torso.mlp": 0.1})
    counts = np.full((1, 4, 128), 2048)
    ctx = {"log": lambda _m: None, "mix": TORSO, "mix_trace": found,
           "batch_size": 2, "trace": object(), "k": 1,
           "route_counts": counts,
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert mix_trace.scope_us(ctx, "torso.mlp") == pytest.approx(0.1e6)
    assert mix_trace.chunk_ms(ctx) == 2000.0
    for kind, spent in ((SLIDING, 0.8), (FULL, 0.4)):
        flops = shapes_mix.attention_counts(TORSO, 2, kind)["flops"]
        assert mix_trace.attention_roofline(ctx, kind) == pytest.approx(
            100 * flops / 197e12 / spent)
    assert mix_trace.step_mfu(ctx) == pytest.approx(
        100 * shapes_mix.step_flops(TORSO, 2, counts) / 197e12 / 2.0)
    assert 25 < mix_trace.step_mfu(ctx) < 32
    # a scope no operation carries reads 0.0, not a division by zero
    assert mix_trace.experts_roofline(ctx) == 0.0


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [CELL])
def test_last_stdout_line_is_the_cells_result_line(cell, trace):
    assert_result_line(rehearse(cell, trace), cell, trace)


@pytest.fixture(scope="module")
def rehearsed():
    """The cell at rehearsal size, its first chunk run and its program
    given up: what ``benchmark/tools/calibrate_controls.py`` does a seed."""
    import time

    from benchmark.drivers import learner_static_mix as driver
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


def test_the_three_controls_fail_where_the_program_passes(rehearsed):
    """bfloat16 as configured stays inside the rehearsal's limits; the
    reference with fp8 product inputs, the reference whose window does not
    cut and the reference that rotates on the full layer too each break at
    least one of them."""
    from benchmark.learner import judge
    from benchmark.tools.calibrate_controls import exceeded

    limits = rehearsed.env.cfg["limits"]
    quiet = lambda _m: None  # noqa: E731
    controls = rehearsed.control_numbers()
    sound = rehearsed.check_first_chunk()
    assert {"bias_gap", "route_hist_gap", "td_gap"} <= set(sound)
    assert judge(sound, limits, quiet), exceeded(sound, limits)
    assert set(controls) == {"fp8", "all_full", "roped_full"}
    for name, numbers in controls.items():
        assert exceeded(numbers, limits), (name, numbers)
        assert not judge(numbers, limits, quiet), name
