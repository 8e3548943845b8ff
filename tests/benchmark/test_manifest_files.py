"""``BENCHMARK.json`` against the files it names: every configuration,
traffic mix, driver and per-layer reader is a file found by its name."""

import os

import pytest

from benchmark import cellbuild, manifest, run

MANIFEST = manifest.load()
ROOT = os.path.join(manifest.REPO, "benchmark")


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_cell_files_are_found_by_name(w):
    cfg = cellbuild.load_config(w["config"], False)
    traffic = cellbuild.load_traffic(w["traffic"], False)
    assert cfg["name"] == w["config"] and traffic["name"] == w["traffic"]
    assert os.path.exists(os.path.join(
        ROOT, "drivers", traffic["driver"] + ".py"))
    assert w["chips"] == 1
    # every departure from the source is written down, in the file and in
    # the manifest alike; a cut names a top-level key and says why
    assert cfg["assumed"]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert entry["file"] == f"benchmark/configs/{w['config']}.json"
    assert entry["reduced"] == cfg["reduced"]
    assert all(key in cfg for key in cfg["reduced"])
    assert bool(cfg["reduced"]) == ("reduced_why" in cfg)
    # no width is ever cut
    assert not {"model", "learner"} & set(cfg["reduced"])


@pytest.mark.parametrize("m", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_each_per_layer_metric_has_a_reader_that_can_find_nothing(m):
    read = run.layer_reader(m["name"])
    # a reader that finds nothing to read returns nothing
    assert read({"log": lambda _m: None}) is None
    e2e = {e["name"]: e for e in MANIFEST["end_to_end"]}
    assert m["moves"] in e2e
    cells = m.get("workloads", [w["name"] for w in MANIFEST["workloads"]])
    moved = e2e[m["moves"]].get("workloads", cells)
    assert set(cells) <= set(moved)


@pytest.mark.parametrize("w", MANIFEST["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_setup_another_metric_and_a_layer(w):
    e2e = manifest.metrics_for(MANIFEST, w["name"], False)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_for(MANIFEST, w["name"], True)


def test_bounds_and_run_seconds_are_inside_the_contract():
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    cells = 24  # the limit is what fits with the full 24 cells
    rs = MANIFEST["run_seconds"]
    assert (2 + 14 * cells) * (rs + 60) + cells * 180 + 1200 <= 43200


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        run.load_peak("TPU v9 imaginary")
    assert run.load_peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
