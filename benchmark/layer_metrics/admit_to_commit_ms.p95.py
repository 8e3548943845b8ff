"""95th percentile of the time from a batch's due time at
``ReplayService.add`` to the chunk hook at which
``IngestOverlap.rows_committed`` first covers it (harness clock)."""

from benchmark.learner import percentile


def read(ctx):
    samples = ctx.get("admit_to_commit_s")
    if samples is None:
        return None
    return percentile(samples, 95, "admit_to_commit_ms.p95") * 1e3
