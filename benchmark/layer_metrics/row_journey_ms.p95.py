"""95th percentile, per followed add, of the time from the start of its
``ingest.admit`` to the end on the device of the first chunk that can sample its
row: the program's own row-to-gradient (the four ``row_*`` hops sum to it row by
row).

0.0 on a program whose spans say no tickets and positions (stderr says so)."""

from benchmark import row_journey


def read(ctx):
    return row_journey.read(ctx, "row_journey_ms.p95")
