"""The sparse-attention torso cell (``humanoid-keye2-ep8.learn-static``): its
configuration file against the published config, the catalog and the
program's own parameter tree, its driver's compared numbers, the operation
counts its rooflines use against brute force, and what the manifest lists
for it (the sound rehearsal of every cell, this one included, is
``test_result_line.py``'s; the files found by name
``test_manifest_files.py``'s)."""

import json
import os

import numpy as np
import pytest

from benchmark import cellbuild, manifest, shapes_sparse

CELL = "humanoid-keye2-ep8.learn-static"
CONFIG = cellbuild.load_config("humanoid-keye2-ep8", False)
TORSO = CONFIG["model"]["torso"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

# the published widths, written out: the file may not drift from them
PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
    "head_dim": 128, "num_experts": 128, "num_experts_per_tok": 8,
    "moe_intermediate_size": 768, "rms_norm_eps": 1e-6,
    "norm_topk_prob": True, "intermediate_size": 6144, "vocab_size": 151936,
    "max_position_embeddings": 262144, "rope_theta": 10000000,
    "num_local_experts": 128, "decoder_sparse_step": 1,
}
SA = {"indexer_head_dim": 64, "indexer_num_heads": 16,
      "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
      "topk": 2048}


@pytest.mark.parametrize("key, value", sorted(PUBLISHED.items()))
def test_every_width_is_as_published(key, value):
    assert CONFIG[key] == value
    if key in TORSO:
        assert TORSO[key] == value


def test_the_index_is_as_published_and_reaches_the_program_whole():
    assert CONFIG["sa_config"] == TORSO["sa_config"] == SA
    assert CONFIG["rope_scaling"]["mrope_section"] == [16, 24, 24]
    rope = TORSO["rope_parameters"]["sparse_attention"]
    assert rope == {"rope_type": "default", "rope_theta": 10000000,
                    "mrope_section": [16, 24, 24]}
    assert TORSO["layer_types"] == ["sparse_attention"] * 4
    assert TORSO["qk_norm"] is True and CONFIG["mlp_only_layers"] == []
    # eight times topk: the selection discards three quarters of the pairs
    assert TORSO["tokens"] == CONFIG["model"]["obs_dim"] == 16384 \
        == 8 * SA["topk"]
    small = cellbuild.load_config("humanoid-keye2-ep8", True)["model"]["torso"]
    assert small["tokens"] >= 4 * small["sa_config"]["topk"]


def test_the_cut_is_written_down_and_keeps_the_floors():
    assert CONFIG["reduced"] == ["num_hidden_layers", "experts_held", "vocab",
                                 "lm_head", "vision_tower"]
    assert CONFIG["published"]["num_hidden_layers"] == 48
    assert CONFIG["published"]["num_experts"] == 128
    assert CONFIG["published"]["vocab_size"] == 151936
    assert CONFIG["num_hidden_layers"] == len(TORSO["layer_types"]) == 4
    lo, hi = CONFIG["experts_held"]
    assert TORSO["experts_held"] == [lo, hi] and hi - lo == 16 >= 8
    assert CONFIG["vocab"] == TORSO["vocab_rows"] == 151936 // 8
    assert TORSO["bins"] == 1024
    assert CONFIG["lm_head"] is False and CONFIG["vision_tower"] is False
    assert "eight" in CONFIG["stands_for"] and CONFIG["limits_why"]
    assert "eleven further pipeline stages" in CONFIG["stands_for"]
    for marked in ("q/k norm", "LayerNorm", "ties to the lower position",
                   "KL(p_t", "bfloat16 inputs", "41 Humanoid-v4 steps"):
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
    assert size(torso["layer_0"]) == here["layer"] == 96899456
    layer = torso["layer_0"]
    assert sum(size(layer[n]) for n in (
        "index_q", "index_k", "index_k_norm", "index_w")) \
        == here["indexer_a_layer"] == 2261120
    assert sum(size(layer[n]) for n in ("gate", "up", "down")) \
        == here["experts_a_layer"] == 16 * 3 * 2048 * 768
    assert size(state.critic_params) + size(state.actor_params) \
        == here["total"]
    assert here["total"] == here["torso"] + here["heads"]
    assert 8.5e9 < 20 * here["total"] < 8.6e9
    # the ring the file states: 16,384 rows of two 16,384-wide fields
    row = 4 * (2 * 16384 + 17 + 3)
    assert row == 131152 and 2.1e9 < row * CONFIG["replay"]["capacity"] \
        < 2.2e9


def test_the_file_holds_every_key_of_the_catalog_entry():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if "Keye-VL-2.0-30B-A3B" in line)
    assert CONFIG["source"].startswith(row["source_url"])
    for key, value in row["config"].items():
        if key not in CONFIG["reduced"]:
            assert CONFIG[key] == value, key


def test_seeded_indexer_leaves_are_datagens_own():
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import learner_static_sparse as driver

    cfg = cellbuild.load_config("humanoid-keye2-ep8", True)
    config = cellbuild.learner_config(cfg)
    _actor, critic = jax.jit(lambda s: driver.seeded_params(config, s))(
        jnp.uint32(12345))
    layer = critic["params"]["torso"]["layer_1"]
    std = lambda x: float(jnp.std(x))  # noqa: E731
    assert std(layer["index_q"]["kernel"]) == pytest.approx(1 / 8, rel=0.1)
    assert std(layer["gate"]["kernel"]) == pytest.approx(1 / 8, rel=0.1)
    assert float(jnp.max(jnp.abs(layer["index_k_norm"]["bias"]))) == 0.0
    assert float(jnp.min(layer["q_norm"]["scale"])) == 1.0
    assert driver.CELL is driver.SparseCell


def test_select_hist_gap_is_a_share_of_a_layers_selections():
    from benchmark.drivers.learner_static_sparse import select_hist_gap

    ref = np.full((4, 32), 1966144)  # 2 x 31,458,304 over 32 blocks
    assert select_hist_gap(ref, ref) == 0.0
    prog = ref.copy()
    prog[1, 3] += 5  # five queries of layer 1 chose a key of block 3 ...
    prog[1, 9] -= 5  # ... where the reference chose one of block 9
    assert select_hist_gap(prog, ref) == pytest.approx(10 / 62916608)


def test_kept_and_causal_pairs_against_brute_force():
    t = np.arange(300)
    dense = t[None] <= t[:, None]
    assert shapes_sparse.causal_pairs(300) == int(dense.sum())
    kept = np.minimum(dense.sum(axis=1), 37)
    assert shapes_sparse.kept_pairs(300, 37) == int(kept.sum())
    assert shapes_sparse.kept_pairs(30, 37) == shapes_sparse.causal_pairs(30)
    # the numbers ISSUE 32 and the cell's why quote
    assert shapes_sparse.causal_pairs(16384) == 134225920
    assert shapes_sparse.kept_pairs(16384, 2048) == 31458304
    counts = np.full((1, 4, 32), 2 * 31458304 / 32)
    assert shapes_sparse.kept_share(TORSO, counts, 2) == pytest.approx(
        23.4368, abs=1e-3)


def test_indexer_and_attention_counts_by_hand():
    # one layer, one sequence, one forward pass, by hand; four layers, five
    # forward-equivalents, batch 2 in the functions
    pairs, kept, t_len = 134225920, 31458304, 16384
    idx = 2 * (t_len * 2048 * (16 * 64 + 64 + 16) + pairs * 16 * 64)
    got = shapes_sparse.indexer_counts(TORSO, 2)
    assert got["flops"] == pytest.approx(5 * 4 * 2 * idx)
    attn = 2 * (t_len * (2 * 2048 * 4096 + 2 * 2048 * 512)
                + kept * 32 * 128 * 2)
    got = shapes_sparse.attention_counts(TORSO, 2)
    assert got["flops"] == pytest.approx(5 * 4 * 2 * attn)
    # selected pairs only: a dense causal kernel would count 4.27 times the
    # pair products
    assert pairs / kept == pytest.approx(4.2668, abs=1e-3)
    # against brute force at a small size: a dense loop over every pair
    small = {**TORSO, "tokens": 48, "layer_types": ["sparse_attention"],
             "sa_config": {**SA, "topk": 10}}
    flops = 0
    for t in range(48):
        for s in range(t + 1):
            flops += 2 * 16 * 64  # one index score
    flops += 2 * 48 * 2048 * (16 * 64 + 64 + 16)
    assert shapes_sparse.indexer_counts(small, 1)["flops"] \
        == pytest.approx(5 * flops)
    flops = 2 * 48 * (2 * 2048 * 4096 + 2 * 2048 * 512)
    for t in range(48):
        flops += min(t + 1, 10) * 32 * 128 * 2 * 2  # q k^T and p v
    assert shapes_sparse.attention_counts(small, 1)["flops"] \
        == pytest.approx(5 * flops)


def test_expert_counts_are_the_mellum_cells_at_this_models_widths():
    counts = np.zeros((1, 4, 128), np.int64)
    counts[:, :, :16] = 2048  # held: an even share of 2 x 16,384 x 8
    counts[:, :, 16:] = 2048
    rows = shapes_sparse.held_assignments(TORSO, counts)
    assert rows == 4 * 16 * 2048
    got = shapes_sparse.expert_counts(TORSO, rows)
    assert got["flops"] == pytest.approx(5 * 2 * rows * 3 * 2048 * 768)
    assert shapes_sparse.load_max_over_mean(TORSO, counts) == 1.0


def test_the_cell_is_one_chip_and_lists_its_ten_layer_metrics():
    man = manifest.load()
    assert manifest.cell(man, CELL)["chips"] == 1
    traced = manifest.metrics_for(man, CELL, True)
    assert set(traced) == {
        "compile_s", "sparse_chunk_device_ms", "indexer_us_per_step",
        "attn_sparse_us_per_step", "sparse_route_us_per_step",
        "sparse_experts_us_per_step", "indexer_roofline",
        "attn_sparse_roofline", "sparse_experts_roofline",
        "select_kept_share", "sparse_expert_load_max_over_mean"}
    assert set(manifest.metrics_for(man, CELL, False)) == {
        "grad_steps_per_s", "setup_s"}
    # the mellum cell's readers are not asked in this cell, nor this
    # cell's in that one
    other = manifest.metrics_for(man, "humanoid-mellum2-ep4.learn-static",
                                 True)
    assert set(other) & set(traced) == {"compile_s"}
    for entry in traced.values():
        if entry["name"] != "compile_s":
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "grad_steps_per_s"


def test_the_readers_read_this_cell_and_no_other():
    """On a context that is another cell's (``torso``, not ``sparse``) every
    reader of this cell returns nothing, and the roofline readers of the
    mellum cell return nothing on this cell's."""
    from benchmark import run, sparse_trace, torso_trace

    log = lambda _m: None  # noqa: E731
    theirs = {"log": log, "trace": object(), "torso": TORSO, "k": 1,
              "chunk_text": "", "chunk_program": "jit_fn",
              "torso_trace": None, "batch_size": 2}
    for name in manifest.metrics_for(manifest.load(), CELL, True):
        if name != "compile_s":
            assert run.layer_reader(name)(dict(theirs)) is None, name
    mine = {"log": log, "trace": object(), "sparse": TORSO, "k": 1,
            "sparse_trace": None, "batch_size": 2}
    assert torso_trace.attn_roofline(dict(mine)) is None
    assert sparse_trace.indexer_roofline(dict(mine)) is None  # no trace read
    counts = np.full((1, 4, 32), 2 * 31458304 / 32)
    assert sparse_trace.kept_share({**mine, "select_counts": counts}) \
        == pytest.approx(23.4368, abs=1e-3)


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
    # the selection's own numbers were compared, beside their limits
    assert "[check] select_hist_gap" in proc.stderr
    assert "[check] index_loss_gap" in proc.stderr
