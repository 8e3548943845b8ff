"""95th percentile of the ``ingest.lock_wait`` spans that began in the window: the
time the learner thread waited for the buffer lock the commit thread holds
while it stages a group.

0.0 on a program whose spans say no tickets and positions (stderr says so)."""

from benchmark import row_journey


def read(ctx):
    return row_journey.read(ctx, "lock_wait_ms.p95")
