"""The reduction from trace to numbers, on hand-made intervals and on a small
``.xplane.pb`` recorded on the v5e by this PR's chip runs (the ingest cell at
rehearsal size, two calls of five chunks; ``benchmark/fixtures/``)."""

import glob
import os

import numpy as np
import pytest

from benchmark import manifest, trace_reduce

FIXTURES = os.path.join(manifest.REPO, "benchmark", "fixtures")


def _trace(ops, mods=(), host=(), window=(0.0, 10.0)):
    return trace_reduce.Trace(
        window=window, op_names=[o[0] for o in ops],
        op_start=np.asarray([o[1] for o in ops], float),
        op_end=np.asarray([o[2] for o in ops], float),
        mod_names=[m[0] for m in mods],
        mod_start=np.asarray([m[1] for m in mods], float),
        mod_end=np.asarray([m[2] for m in mods], float),
        host=list(host), n_device_planes=1)


def test_busy_is_the_union_of_intervals_not_their_sum():
    # two overlapping ops, one nested, one apart, one outside the window
    tr = _trace([("a", 1.0, 3.0), ("b", 2.0, 4.0), ("c", 2.5, 2.6),
                 ("d", 6.0, 7.0), ("e", 11.0, 12.0)])
    busy, window = trace_reduce.busy_and_window(tr)
    assert window == 10.0
    assert busy == pytest.approx(4.0)  # [1, 4] and [6, 7]; the sum is 6.1


def test_busy_is_clipped_to_the_window():
    tr = _trace([("a", -1.0, 1.0), ("b", 9.5, 12.0)])
    busy, window = trace_reduce.busy_and_window(tr)
    assert busy == pytest.approx(1.5) and busy <= window


def test_program_runs_are_found_by_name_inside_the_window():
    tr = _trace([], mods=[("jit_fn(1)", 1.0, 2.0), ("jit__lambda(2)", 2.0, 2.1),
                          ("jit_fn(1)", 2.5, 3.5), ("jit_fn(1)", 9.5, 10.5)])
    start, end = trace_reduce.program_runs(tr, "jit_fn")
    assert start.tolist() == [1.0, 2.5] and end.tolist() == [2.0, 3.5]


def test_idle_gaps_go_to_the_innermost_annotation():
    tr = _trace([("a", 0.0, 1.0), ("b", 2.0, 3.0), ("c", 5.0, 10.0)],
                host=[("bench.dispatch", 0.5, 6.0),
                      ("bench.wait_prev", 3.2, 4.8)])
    gaps = dict(trace_reduce.idle_by_host(tr))
    assert gaps == {"bench.dispatch": pytest.approx(1.0),
                    "bench.wait_prev": pytest.approx(2.0)}


def test_top_ops_sum_same_named_events():
    tr = _trace([("fusion.1", 0.0, 1.0), ("fusion.1", 2.0, 3.5),
                 ("copy.2", 4.0, 4.5)])
    assert trace_reduce.top_ops(tr, 8) == [["fusion.1", 2.5], ["copy.2", 0.5]]


@pytest.fixture(scope="module")
def recorded():
    paths = glob.glob(os.path.join(FIXTURES, "*.xplane.pb.gz"))
    assert paths, "no recorded trace under benchmark/fixtures"
    assert os.path.getsize(paths[0]) < 1 << 20
    return trace_reduce.load(paths[0])


def test_recorded_trace_reads_the_device_plane(recorded):
    assert recorded.n_device_planes >= 1
    assert len(recorded.op_names) > 100
    busy, window = trace_reduce.busy_and_window(recorded)
    assert 0 < busy <= window
    # a sum over events would count overlapping lines twice; the union of
    # the ops line alone can never exceed the window
    lo, hi = recorded.window
    summed = float(np.sum(np.clip(recorded.op_end, lo, hi)
                          - np.clip(recorded.op_start, lo, hi)))
    assert busy <= summed + 1e-12


def test_recorded_trace_finds_the_chunk_program_by_name(recorded):
    start, end = trace_reduce.program_runs(recorded, "jit_fn")
    assert start.size == 10  # two calls of five chunks
    assert np.all(end > start) and np.all(start[1:] >= end[:-1])
    gaps = start[1:] - end[:-1]
    assert np.all(gaps >= 0) and np.median(gaps) < 0.05


def test_recorded_gaps_are_attributed_to_the_harness_annotations(recorded):
    names = {h[0] for h in recorded.host}
    assert {"bench.dispatch", "bench.wait_prev", "bench.commit",
            "bench.stage"} <= names
    gaps = trace_reduce.idle_by_host(recorded)
    busy, window = trace_reduce.busy_and_window(recorded)
    assert sum(g[1] for g in gaps) == pytest.approx(window - busy, rel=1e-6)
    assert any(g[0].startswith("bench.") for g in gaps)
    assert trace_reduce.top_ops(recorded, 8)
