"""95th percentile, per followed add, of the time from the end of its
``ingest.host_stage`` to the start of the ``fused.stage_block`` whose positions
hold its row (``staging_wait_ms`` stays the per-block oldest-row median).

0.0 on a program whose spans say no tickets and positions (stderr says so)."""

from benchmark import row_journey


def read(ctx):
    return row_journey.read(ctx, "row_staging_ms.p95")
