"""The indexers' share of their roofline: the least time the chip could
take for a step's index projections and index scores over the causal pairs
(``benchmark/shapes_sparse.indexer_counts``: five forward-equivalents,
nothing recomputed, the selection and the alignment target not counted)
over the time under ``torso.indexer``. No clamp."""

from benchmark import sparse_trace


def read(ctx):
    return sparse_trace.indexer_roofline(ctx)
