"""``calibrate_cell.py`` for a cell whose driver brings more than one
control: ``control_numbers()`` of its ``CELL`` hands back ``{control name:
numbers}`` (``drivers/learner_static_linear.py``: ``fp8``, the reference with
fp8 product inputs; ``reset64``, the reference whose recurrent state is set
to zero at every 64th token). One process, on the chip at the cell's own
size:

    python -m benchmark.tools.calibrate_controls <workload> <controls> <seed> ...

Every seed gives a sound reading (the program against the reference); the
first ``<controls>`` seeds also one reading a control. Prints one JSON line
per seed with, per control, the limits of the configuration it exceeds
(``fails``: a control that fails none guards nothing), and last, per number,
the sound runs' largest reading and each control's smallest.
"""

from __future__ import annotations

import importlib
import json
import sys
import time

_T = time.perf_counter()


def exceeded(numbers: dict, limits: dict) -> list:
    """Names of the numbers over their limit (a ``null`` limit holds
    none)."""
    return sorted(k for k, v in numbers.items()
                  if limits.get(k) is not None and not v <= limits[k])


def main(argv) -> int:
    from d4pg_tpu import startup

    from benchmark import cellbuild, manifest
    from benchmark.learner import RunEnv

    workload, n_controls = argv[0], int(argv[1])
    seeds = [int(s) for s in argv[2:]]
    device = startup.start("tpu")
    cell = manifest.cell(manifest.load(), workload)
    cfg = cellbuild.load_config(cell["config"], False)
    traffic = cellbuild.load_traffic(cell["traffic"], False)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    sound, control = {}, {}
    for i, seed in enumerate(seeds):
        env = RunEnv(cell=cell, cfg=cfg, traffic=traffic, seed=seed,
                     seconds=0.0, trace=False, rehearsal=False, fault="",
                     t_start=_T, trace_dir="", wanted=frozenset(),
                     compile_seconds=lambda: 0.0,
                     log=lambda m: print(m, file=sys.stderr, flush=True))
        lc = driver.CELL(env)
        lc.first_chunk()
        lc.release()
        t = time.perf_counter()
        # the controls first: they leave the exact reference for the check
        bad = lc.control_numbers() if i < n_controls else {}
        good = lc.check_first_chunk()
        took = time.perf_counter() - t
        print(json.dumps({
            "seed": seed, "kind": device["kind"], "references_s": took,
            "sound": good, "sound_fails": exceeded(good, cfg["limits"]),
            "control": bad, "fails": {
                name: exceeded(numbers, cfg["limits"])
                for name, numbers in bad.items()}}), flush=True)
        for k, v in good.items():
            sound[k] = max(sound.get(k, 0.0), v)
        for name, numbers in bad.items():
            least = control.setdefault(name, {})
            for k, v in numbers.items():
                least[k] = min(least.get(k, float("inf")), v)
    print(json.dumps({"sound_largest": sound, "control_smallest": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
