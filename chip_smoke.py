"""Chip smoke: the quickest proof that the system still starts on the TPU.

    python3 chip_smoke.py

One process, no child. It requires the chip (no accelerator, or a
directory without the ``d4pg_tpu`` package, is a non-zero exit with no
result line), then:

  1. trains the flagship configuration at full width through the normal
     entry point, ``d4pg_tpu.train.main``: Humanoid-v4 (obs 376, act 17),
     256x256x256 actor and critic, 51 atoms, batch 256, bfloat16 compute,
     a 1M-row prioritized ring in HBM (~3.1 GB), K=40 fused updates per
     dispatch, in-process actors, the default ``auto`` selectors. Depth is
     cut, width is not: 5000 warm-up transitions, one epoch of three
     cycles x 80 train steps (two K=40 dispatches each), one eval trial
     and one checkpoint per cycle (the default cadence: a save is then
     followed by a dispatch that donates the saved state);
  2. checks what came out (see ``check_train``).

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, with the
device as JAX reports it. Any failed check prints what failed and exits 1.
The run directory goes under ``chiprun_out/`` next to this file (ignored
by git), never into a tracked path.

``tests/test_chip_smoke.py`` drives ``run()`` at ``TINY`` size on the CPU,
so this file's control flow is exercised before chip time is spent on it.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import sys
import time

# Full width of the flagship model; depth (warm-up, cycles, steps) cut.
FULL = {
    "env": "Humanoid-v4", "bsize": 256, "rmsize": 1_000_000,
    "warmup": 5000, "updates_per_dispatch": 40, "n_cycles": 3,
    "train_steps_per_cycle": 80, "episodes_per_cycle": 2,
    "extra": ["--compute_dtype", "bfloat16"],
}
# Same control flow at a size the CPU sandbox finishes in seconds. The
# device ring is requested explicitly: off-accelerator 'auto' resolves it
# to host, and the assertions below are about the fused device path.
TINY = {
    "env": "point", "bsize": 16, "rmsize": 2000, "warmup": 100,
    "updates_per_dispatch": 8, "n_cycles": 3, "train_steps_per_cycle": 16,
    "episodes_per_cycle": 1,
    "extra": ["--replay_storage", "device", "--max_steps", "20",
              "--num_envs", "2", "--n_atoms", "11", "--v_min", "-5",
              "--v_max", "0"],
}


def train_argv(size: dict, platform: str, log_dir: str) -> list[str]:
    """The ``d4pg_tpu.train`` command line for ``size``: selectors stay at
    their defaults (``--replay_storage auto --fused_replay auto``) unless
    ``size['extra']`` says otherwise."""
    return [
        "--platform", platform, "--env", size["env"],
        "--bsize", str(size["bsize"]), "--rmsize", str(size["rmsize"]),
        "--p_replay", "1", "--warmup", str(size["warmup"]),
        "--updates_per_dispatch", str(size["updates_per_dispatch"]),
        "--n_eps", "1", "--n_cycles", str(size["n_cycles"]),
        "--train_steps_per_cycle", str(size["train_steps_per_cycle"]),
        "--episodes_per_cycle", str(size["episodes_per_cycle"]),
        "--eval_trials", "1", "--log_dir", log_dir, *size["extra"],
    ]


def check_train(result: dict, size: dict, platform: str, run_dir: str,
                crashes: int) -> list[str]:
    """Every miss as one line; empty means the train phase passed.
    ``crashes``: thread crashes contained during the run."""
    failures = []
    want_step = size["n_cycles"] * size["train_steps_per_cycle"]
    if result["learner_step"] != want_step:
        failures.append(f"learner step {result['learner_step']} != "
                        f"cycles x steps = {want_step}")
    for name in ("critic_loss", "actor_loss"):
        if not math.isfinite(result[name]):
            failures.append(f"{name} is not finite: {result[name]}")
    plan = result["plan"]
    if plan["storage"] != "device" or not plan["fused"]:
        failures.append(f"replay did not resolve to the fused device ring: "
                        f"{plan}")
    if plan["K"] != size["updates_per_dispatch"]:
        failures.append(f"K={plan['K']}, asked for "
                        f"{size['updates_per_dispatch']}")
    for what in ("state_on", "ring_on"):
        if plan.get(what) != platform:
            failures.append(f"{what}={plan.get(what)!r}, expected "
                            f"{platform!r}")
    if "avg_test_reward" not in result:
        # AsyncEvaluator contains its failures; a missing eval result is
        # how a swallowed one shows
        failures.append("no avg_test_reward in the final metrics: the "
                        "evaluator never completed a trial")
    if crashes:
        failures.append(f"threads.contained_crashes = {crashes}")
    late = result["compiles_by_cycle"][1:]
    if any(late):
        failures.append(f"learner recompiled after the first cycle: "
                        f"compiles_by_cycle={result['compiles_by_cycle']}")
    ckpt = os.path.join(run_dir, "ckpt", str(want_step))
    if not os.path.isdir(ckpt):
        failures.append(f"no checkpoint of the final step at {ckpt}")
    return failures


def run(size: dict, platform: str, out_dir: str) -> list[str]:
    """The smoke's body: train through ``d4pg_tpu.train.main`` and check
    the result. Returns every failure as one line."""
    import jax

    from d4pg_tpu import train
    from d4pg_tpu.config import parse_args
    from d4pg_tpu.obs.registry import REGISTRY

    log_dir = os.path.join(out_dir, "runs")
    # a previous smoke's checkpoints would collide with this run's steps
    shutil.rmtree(log_dir, ignore_errors=True)
    argv = train_argv(size, platform, log_dir)
    print("[smoke] python -m d4pg_tpu.train " + " ".join(argv), flush=True)
    contained = REGISTRY.counter("threads.contained_crashes")
    crashes_before = contained.value
    t0 = time.perf_counter()
    result = train.main(argv)
    print(f"[smoke] train.main returned after "
          f"{time.perf_counter() - t0:.1f} s (compilation included)",
          flush=True)
    cfg = parse_args(argv).resolve()
    run_dir = os.path.join(log_dir, cfg.run_name())
    failures = check_train(result, size, platform, run_dir,
                           contained.value - crashes_before)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke] device memory: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    if not failures:
        # three checkpoints of a run that passed are ~20 MB nobody reads;
        # a failed run keeps its directory for the post-mortem
        shutil.rmtree(log_dir, ignore_errors=True)
    return failures


def main() -> int:
    t0 = time.perf_counter()
    from d4pg_tpu import startup

    device = startup.start()  # raises when the chip cannot initialise
    if device["platform"] != "tpu":
        print(f"chip_smoke needs the TPU; the default backend is "
              f"{device['platform']!r}", file=sys.stderr)
        return 1
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out", "chip_smoke")
    failures = run(FULL, "tpu", out_dir)
    print(f"[smoke] total {time.perf_counter() - t0:.1f} s", flush=True)
    if failures:
        for line in failures:
            print(f"[smoke] FAILED: {line}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        k: device[k] for k in ("platform", "kind", "count")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
