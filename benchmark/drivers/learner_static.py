"""The learner against a ring filled before the window: no ingest, the
fused chunk does all the work (``FusedLoop(service=None)``)."""

from __future__ import annotations

from benchmark.learner import LearnerCell, RunEnv, report


def run(env: RunEnv) -> dict:
    cell = LearnerCell(env)
    cell.first_chunk()
    cell.warm()
    window = cell.run_window()
    return report(cell, window, attempted=window["chunks"],
                  failed=window["nonfinite_chunks"])
