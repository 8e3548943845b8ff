"""Median host time per chunk inside ``loop.ingest.commit`` plus
``loop.ingest.stage`` (harness spans round the two calls)."""

import numpy as np


def read(ctx):
    spans = ctx.get("spans")
    if not spans or not spans["commit"] or not spans["stage"]:
        return None
    n = min(len(spans["commit"]), len(spans["stage"]))
    per_chunk = np.asarray(spans["commit"][-n:]) + np.asarray(
        spans["stage"][-n:])
    return float(np.median(per_chunk) * 1e3)
