"""``calibrate.py`` for a cell whose driver brings its own cell class (its
own reference, weights and compared numbers): the class is the driver
module's ``CELL`` (``LearnerCell`` where it names none). One process, on the
chip at the cell's own size:

    python -m benchmark.tools.calibrate_cell <workload> <controls> <seed> ...

Every seed gives a sound reading (the program against the reference); the
first ``<controls>`` seeds also a control reading (the reference with fp8
inputs in the program's place). Prints one JSON line per seed and, last, per
number the sound runs' largest reading and the control's smallest.
"""

from __future__ import annotations

import importlib
import json
import sys
import time

_T = time.perf_counter()


def main(argv) -> int:
    from d4pg_tpu import startup

    from benchmark import cellbuild, manifest
    from benchmark.learner import LearnerCell, RunEnv

    workload, n_controls = argv[0], int(argv[1])
    seeds = [int(s) for s in argv[2:]]
    device = startup.start("tpu")
    cell = manifest.cell(manifest.load(), workload)
    cfg = cellbuild.load_config(cell["config"], False)
    traffic = cellbuild.load_traffic(cell["traffic"], False)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    cell_class = getattr(driver, "CELL", LearnerCell)
    sound, control = {}, {}
    for i, seed in enumerate(seeds):
        env = RunEnv(cell=cell, cfg=cfg, traffic=traffic, seed=seed,
                     seconds=0.0, trace=False, rehearsal=False, fault="",
                     t_start=_T, trace_dir="", wanted=frozenset(),
                     compile_seconds=lambda: 0.0,
                     log=lambda m: print(m, file=sys.stderr, flush=True))
        lc = cell_class(env)
        lc.first_chunk()
        lc.release()
        t = time.perf_counter()
        good = lc.check_first_chunk()
        took = time.perf_counter() - t
        bad = lc.control_numbers() if i < n_controls else {}
        print(json.dumps({"seed": seed, "kind": device["kind"],
                          "check_s": took, "sound": good, "control": bad}),
              flush=True)
        for k, v in good.items():
            sound[k] = max(sound.get(k, 0.0), v)
        for k, v in bad.items():
            control[k] = min(control.get(k, float("inf")), v)
    print(json.dumps({"sound_largest": sound, "control_smallest": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
