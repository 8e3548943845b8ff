"""95th percentile, per followed add, of the time from the start of its block's
``fused.commit_staged`` to the end ON THE DEVICE of the chunk dispatched after
it: the commit program, what was left of the chunk already queued, and the
chunk itself.

0.0 on a program whose spans say no tickets and positions (stderr says so)."""

from benchmark import row_journey


def read(ctx):
    return row_journey.read(ctx, "row_land_to_done_ms.p95")
