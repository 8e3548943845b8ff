"""Record the fixture ``tests/benchmark/test_row_journey.py`` reads: the run
of ``record_program_fixture`` (the ingest cell at rehearsal size ON THE CHIP,
traced) by a program whose spans say tickets and positions, with what
``row_journey`` read from it beside the trace.

    python -m benchmark.tools.record_row_journey_fixture <out_dir> [<seed> [cpu]]

Writes ``<out_dir>/ingest-rehearsal.xplane.pb.gz`` and ``fixture.json`` (what
``record_program_fixture`` writes there, less the two compiled texts, which no
reader of the journey needs, plus a ``row_journey`` block: the seven numbers
and the counts of adds followed, dropped and still on their way).
"""

from __future__ import annotations

import json
import os
import sys


def main(argv) -> int:
    from benchmark import row_journey, trace_reduce
    from benchmark.tools import record_program_fixture

    out_dir = argv[0]
    code = record_program_fixture.main(argv)
    if code:
        return code
    for name in ("chunk.hlo.txt.gz", "commit.hlo.txt.gz"):
        os.remove(os.path.join(out_dir, name))
    path = os.path.join(out_dir, "ingest-rehearsal.xplane.pb.gz")
    with open(os.path.join(out_dir, "fixture.json")) as f:
        meta = json.load(f)
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    ctx = {"trace": trace_reduce.load(path), "xplane_path": path,
           "k": meta["k"], "chunk_program": meta["chunk_program"],
           "log": log, "chunk_text": {}, "commit_text": {}}
    read = row_journey.analyse(ctx)
    got = row_journey.follow(ctx["program_trace"]["spans"], ctx["trace"],
                             meta["chunk_program"])
    meta["row_journey"] = {
        "read": read, "followed": int(got["rows"].shape[0]),
        **{k: got[k] for k in ("admitted", "dropped", "on_the_way", "pairs",
                               "dispatches")}}
    with open(os.path.join(out_dir, "fixture.json"), "w") as f:
        json.dump(meta, f, indent=1)
    log(f"[fixture] row_journey: {meta['row_journey']}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
