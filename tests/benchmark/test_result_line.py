"""The real command, as a subprocess, in rehearsal mode: the last line of
its real stdout is parsed and checked with the harness's own validator for
every cell in both trace modes; injected failures must leave no line.

Rehearsal: tiny sizes from each file's ``rehearsal`` block, the CPU backend,
a 2 s window; the line names platform ``cpu``. Nothing here touches a TPU
topology, at import or later.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

REPO = manifest.REPO
MANIFEST = manifest.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def rehearse(cell, trace, fault=""):
    # one device (the suite's conftest asks for eight) and one compute
    # thread: the rehearsal shares its cores with the other test workers
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_cpu_multi_thread_eigen=false",
               PYTHONPATH=REPO, BENCH_RUN="ignored")
    cmd = [sys.executable, *MANIFEST["command"][1:], "--workload", cell,
           "--seed", "2147483659", "--seconds", "2", "--trace", str(trace),
           "--rehearsal", "1"]
    if fault:
        cmd += ["--fault", fault]
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=170)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_last_stdout_line_is_the_cells_result_line(cell, trace):
    proc = rehearse(cell, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.splitlines()
    # the result line is all the real stdout ever carries
    assert len(lines) == 1, lines
    obj = json.loads(lines[-1])
    manifest.validate_line(MANIFEST, cell, bool(trace), obj, platform="cpu")
    assert obj["correct"] is True and obj["failed"] == 0
    assert obj["attempted"] > 0
    if trace:
        assert 0 < obj["device"]["busy_s"] <= obj["device"]["window_s"]
        assert obj["breakdown"]["device_ops"]
    # each number compared is printed beside its limit
    assert "[check] critic_loss_gap" in proc.stderr


@pytest.mark.parametrize("fault, reason", [
    ("nan_loss", "non-finite loss"),
    ("no_samples", "samples, under the"),
    ("unknown_kind", "is not in benchmark/peaks.json"),
    # the timed path broken underneath: the chunk hands back the state it
    # was given, and the comparison with the reference must say so
    ("frozen_step", "correct=false"),
])
def test_a_failure_exits_nonzero_and_prints_no_line(fault, reason):
    proc = rehearse(CELLS[0], 0, fault)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert reason in proc.stderr, proc.stderr[-3000:]
    if fault == "frozen_step":
        assert "update_gap" in proc.stderr and "EXCEEDED" in proc.stderr


def test_fault_injection_is_refused_outside_rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--fault",
         "nan_loss"], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=60)
    assert proc.returncode != 0 and proc.stdout == ""


def test_no_chip_is_a_nonzero_exit_with_no_line():
    # without --rehearsal the TPU is required; this sandbox has none
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no accelerator" in proc.stderr


def _line(**over):
    obj = {"correct": True, "attempted": 10, "failed": 0,
           "metrics": {"grad_steps_per_s": {"value": 2.0, "unit": "steps/s"},
                       "chunk_ms.p95": {"value": 1.0, "unit": "ms"},
                       "setup_s": {"value": 3.0, "unit": "s"}},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 5}}
    obj.update(over)
    return obj


def test_validator_accepts_a_sound_line():
    manifest.validate_line(MANIFEST, CELLS[0], False, _line())


@pytest.mark.parametrize("spoil", [
    lambda o: o["metrics"].pop("setup_s"),
    lambda o: o["metrics"].update(extra={"value": 1.0, "unit": "s"}),
    lambda o: o["metrics"]["setup_s"].update(value=float("nan")),
    lambda o: o["metrics"]["setup_s"].update(value=float("inf")),
    lambda o: o["metrics"]["setup_s"].update(value=3),
    lambda o: o["metrics"]["setup_s"].update(unit="ms"),
    lambda o: o["metrics"]["chunk_ms.p95"].update(value=0.0),
    lambda o: o.update(correct=1),
    lambda o: o.update(attempted=1.5),
    lambda o: o.update(failed=11),
    lambda o: o["device"].update(count=4),
    lambda o: o["device"].update(platform="cpu"),
    lambda o: o["device"].pop("memory_peak_bytes"),
    lambda o: o.pop("device"),
], ids=lambda f: None)
def test_validator_refuses_a_spoilt_line(spoil):
    import numpy as np  # a numpy scalar is not a plain float

    obj = _line()
    spoil(obj)
    with pytest.raises(manifest.LineError):
        manifest.validate_line(MANIFEST, CELLS[0], False, obj)
    obj = _line()
    obj["metrics"]["setup_s"]["value"] = np.float32(3.0)
    with pytest.raises(manifest.LineError):
        manifest.validate_line(MANIFEST, CELLS[0], False, obj)


@pytest.mark.parametrize("busy, window", [(0.0, 1.0), (1.5, 1.0),
                                          (float("nan"), 1.0)])
def test_validator_refuses_busy_outside_the_window(busy, window):
    traced = {m["name"]: {"value": 1.0, "unit": m["unit"]}
              for m in manifest.metrics_for(MANIFEST, CELLS[0],
                                            True).values()}
    obj = _line(metrics=traced)
    obj["device"].update(busy_s=busy, window_s=window)
    with pytest.raises(manifest.LineError):
        manifest.validate_line(MANIFEST, CELLS[0], True, obj)
    obj["device"].update(busy_s=0.5, window_s=1.0)
    manifest.validate_line(MANIFEST, CELLS[0], True, obj)


@pytest.mark.parametrize("depth", [1, 3])
def test_chunk_clock_keeps_depth_chunks_queued(depth):
    """The hook of chunk t waits for chunk t-depth and no later one; the
    end of the window waits for the rest, one stamp a chunk."""
    import contextlib
    import types

    import jax.numpy as jnp

    from benchmark.learner import ChunkClock

    clock = ChunkClock(lambda _name: contextlib.nullcontext())
    clock.depth = depth
    state = types.SimpleNamespace(critic_params={"w": jnp.ones((2, 2))})
    for t in range(5):
        clock.on_chunk(state, 4)
        assert len(clock.dispatched) == t + 1
        assert len(clock.done) == max(0, t + 1 - depth)
    assert clock.finish() == clock.done[-1]
    assert len(clock.done) == len(clock.marks) == 5
    assert clock.done == sorted(clock.done)
    assert clock.nonfinite() == 0
