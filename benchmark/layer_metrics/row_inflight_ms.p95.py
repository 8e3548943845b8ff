"""95th percentile, per followed add, of the time from the start of the
``fused.stage_block`` that carries its row to the start of the
``fused.commit_staged`` of the same block.

0.0 on a program whose spans say no tickets and positions (stderr says so)."""

from benchmark import row_journey


def read(ctx):
    return row_journey.read(ctx, "row_inflight_ms.p95")
