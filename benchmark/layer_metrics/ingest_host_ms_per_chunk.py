"""Median over the window's ``learner.chunk`` spans of the durations of the
``ingest.commit`` and ``ingest.stage`` spans inside each: the program's own twin
of ``stage_commit_ms``.

0.0 on a program whose spans say no tickets and positions (stderr says so)."""

from benchmark import row_journey


def read(ctx):
    return row_journey.read(ctx, "ingest_host_ms_per_chunk")
