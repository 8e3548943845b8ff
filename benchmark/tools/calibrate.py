"""Readings a limit is set from: for one cell and several seeds, the numbers
the check compares when the program is sound, and the same numbers when the
control (the reference with fp8 matmul inputs) stands in the program's place.
One process, on the chip at the cell's own size:

    python -m benchmark.tools.calibrate <workload> <seed> [<seed> ...]

Prints one JSON line per seed and, last, per number the sound runs' largest
reading and the control's smallest. PERF.md records what each limit in the
configuration files was set from.
"""

from __future__ import annotations

import json
import sys
import time

_T = time.perf_counter()


def main(argv) -> int:
    from d4pg_tpu import startup

    from benchmark import cellbuild, manifest
    from benchmark.learner import LearnerCell, RunEnv

    workload, seeds = argv[0], [int(s) for s in argv[1:]]
    device = startup.start("tpu")
    cell = manifest.cell(manifest.load(), workload)
    cfg = cellbuild.load_config(cell["config"], False)
    traffic = cellbuild.load_traffic(cell["traffic"], False)
    sound, control = {}, {}
    for i, seed in enumerate(seeds):
        env = RunEnv(cell=cell, cfg=cfg, traffic=traffic, seed=seed,
                     seconds=0.0, trace=False, rehearsal=False, fault="",
                     t_start=_T, trace_dir="", wanted=frozenset(), compile_seconds=lambda: 0.0,
                     log=lambda m: print(m, file=sys.stderr, flush=True))
        lc = LearnerCell(env)
        lc.first_chunk()
        lc.release()
        good = lc.check_first_chunk()
        # the control costs a second and a third reference pass: every
        # seed gives a sound reading, the first few a control reading
        bad = lc.control_numbers() if i < 4 else {}
        print(json.dumps({"seed": seed, "kind": device["kind"],
                          "sound": good, "control": bad}), flush=True)
        for k, v in good.items():
            sound[k] = max(sound.get(k, 0.0), v)
        for k, v in bad.items():
            control[k] = min(control.get(k, float("inf")), v)
    print(json.dumps({"sound_largest": sound, "control_smallest": control}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
