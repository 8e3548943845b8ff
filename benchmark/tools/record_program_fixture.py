"""Record the fixture ``tests/benchmark/test_program_trace.py`` reads: the
ingest cell at rehearsal size ON THE CHIP, traced, with the compiled text of
its two programs beside the trace.

    python -m benchmark.tools.record_program_fixture <out_dir> [<seed> [cpu]]

(``cpu`` only tries the tool where there is no chip; a fixture is from a chip.)

The run is the ingest driver's own, through ``LearnerCell``; only the sizes
(each file's ``rehearsal`` block) and the traffic's pace differ from the cell:
four calls of five chunks, adds fast enough that a block is staged and
committed round most of the ~1.6 ms chunks, and no 50 ms lead before the
first add (the whole window is ~35 ms on the chip), so the trace holds
``jit_commit`` executions between chunk programs and ``fused.*`` spans with
their stats.
Writes ``<out_dir>/ingest-rehearsal.xplane.pb.gz``, ``chunk.hlo.txt.gz``,
``commit.hlo.txt.gz`` and ``fixture.json`` (K, the chunk program's prefix and
what ``program_trace`` read from the live run, for the test to compare).
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import time

_T = time.perf_counter()


def main(argv) -> int:
    from d4pg_tpu import startup

    from benchmark import cellbuild, manifest, program_trace, trace_reduce
    from benchmark.drivers import learner_ingest
    from benchmark.learner import RunEnv

    out_dir = argv[0]
    seed = int(argv[1]) if len(argv) > 1 else 2147483659
    device = startup.start(argv[2] if len(argv) > 2 else "tpu")
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    workload = "humanoid-mlp.learn-ingest"
    man = manifest.load()
    cell = manifest.cell(man, workload)
    cfg = cellbuild.load_config(cell["config"], True)
    traffic = cellbuild.load_traffic(cell["traffic"], True)
    traffic.update(chunks_per_call=5, trace_calls=4, adds_per_s=400)

    def start(self):  # the driver's, without its lead of 50 ms
        self.t_start = time.perf_counter() + 0.001
        for t in self.threads:
            t.start()

    learner_ingest.Actors.start = start
    trace_dir = os.path.join(manifest.REPO, ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    env = RunEnv(cell=cell, cfg=cfg, traffic=traffic, seed=seed, seconds=2.0,
                 trace=True, rehearsal=True, fault="", t_start=_T,
                 trace_dir=trace_dir,
                 wanted=frozenset(manifest.metrics_for(man, workload, True)),
                 compile_seconds=lambda: 0.0, log=log)
    result = learner_ingest.run(env)
    if not result["correct"]:
        log("[fixture] the run was not correct")
        return 3
    from d4pg_tpu.obs import trace as program

    path = trace_reduce.newest_xplane(trace_dir)
    tr = trace_reduce.load(path)
    ctx = dict(result["layer_ctx"])
    ctx.update(trace=tr, xplane_path=path)
    read = program_trace.analyse(ctx)
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "rb") as src, gzip.open(os.path.join(
            out_dir, "ingest-rehearsal.xplane.pb.gz"), "wb", 9) as dst:
        shutil.copyfileobj(src, dst)
    for name, table in (("chunk", program_trace.CHUNK_TABLE),
                        ("commit", program_trace.COMMIT_TABLE)):
        with gzip.open(os.path.join(out_dir, name + ".hlo.txt.gz"), "wt",
                       compresslevel=9) as f:
            f.write(program.compiled_text(table))
    with open(os.path.join(out_dir, "fixture.json"), "w") as f:
        json.dump({"k": ctx["k"], "chunk_program": ctx["chunk_program"],
                   "chunks": int(traffic["chunks_per_call"]
                                 * traffic["trace_calls"]),
                   "device": device["kind"], "seed": seed,
                   "read": {k: v for k, v in read.items() if k != "spans"}},
                  f, indent=1)
    log(f"[fixture] wrote {sorted(os.listdir(out_dir))}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
