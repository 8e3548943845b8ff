"""Record the fixture ``tests/benchmark/test_startup_phases.py`` reads: one
traced rehearsal of a cell (the CPU, tiny sizes: a fixture of host clocks,
not a device number), with the program's start-up log, the trace's spans that
``startup_phases`` reads, the window, and what it read from them.

    python -m benchmark.tools.record_startup_fixture <out.json> [<cell> [<seed>]]

Entries that began after the window's first chunks are cut (a traced window
fills the log; the reader needs set-up and a few spans that are in both).
"""

from __future__ import annotations

import json
import os
import runpy
import sys

KEEP_AFTER = 40  # entries kept past set-up's end


def main(argv) -> int:
    from benchmark import startup_phases

    out = os.path.abspath(argv[0])
    cell = argv[1] if len(argv) > 1 else "humanoid-mlp.learn-static"
    seed = argv[2] if len(argv) > 2 else "2147483659"
    inner = startup_phases.reduce

    def recording(snap, spans, window):
        got = inner(snap, spans, window)
        end = snap["epoch"] + got["setup_s"]
        after = [e for e in snap["entries"] if e[1] >= end][:KEEP_AFTER]
        last = after[-1][1] if after else end
        kept = dict(snap, entries=[e for e in snap["entries"]
                                   if e[1] <= last])
        names = startup_phases.PAIRED + (startup_phases.RUN,)
        fixture = {
            "cell": cell, "seed": int(seed), "platform": "cpu (rehearsal)",
            "log": kept, "window": list(window),
            "spans": [s for s in spans if s[0] in names],
            "read": {m: got[m] for m in startup_phases.METRICS},
            "setup_s": got["setup_s"],
            "phases": got["phases"],
        }
        with open(out, "w") as f:
            json.dump(fixture, f, separators=(",", ":"))
        startup_phases.say(f"fixture written to {out}: "
                           f"{len(kept['entries'])} entries")
        return got

    startup_phases.reduce = recording
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")
    sys.argv = ["benchmark.run", "--workload", cell, "--seed", seed,
                "--seconds", "2", "--trace", "1", "--rehearsal", "1"]
    runpy.run_module("benchmark.run", run_name="__main__")
    return 0  # not reached: the run leaves with os._exit


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
