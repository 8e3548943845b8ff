"""95th percentile, over the adds admitted in the traced window and followed to a
chunk's end, of the time from the start of the add's ``ingest.admit`` to the end
of the ``ingest.host_stage`` that holds its ticket: the wait for a deque slot,
the admission queue, decode, the commit thread's wait for the buffer lock and
its push into host staging.

0.0 on a program whose spans say no tickets and positions (stderr says so)."""

from benchmark import row_journey


def read(ctx):
    return row_journey.read(ctx, "row_queue_ms.p95")
