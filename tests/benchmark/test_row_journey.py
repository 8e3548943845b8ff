"""``benchmark/row_journey.py``: the row's journey on hand-made spans, on a
trace recorded on the v5e by PR 36's chip run of
``benchmark/tools/record_row_journey_fixture.py`` (the ingest cell at
rehearsal size by a program whose spans say tickets and positions;
``benchmark/fixtures/row_journey/``), and on PR 25's fixture, recorded by a
program that says neither, where every new reader must read 0.0."""

import json
import os

import numpy as np
import pytest

from benchmark import manifest, program_trace, row_journey, trace_reduce
from benchmark.run import layer_reader

FIXTURE = os.path.join(manifest.REPO, "benchmark", "fixtures", "row_journey")
OLD_FIXTURE = os.path.join(manifest.REPO, "benchmark", "fixtures",
                           "program_trace")
XPLANE = "ingest-rehearsal.xplane.pb.gz"


def _ctx(directory, lines):
    with open(os.path.join(directory, "fixture.json")) as f:
        meta = json.load(f)
    path = os.path.join(directory, XPLANE)
    return {"trace": trace_reduce.load(path), "xplane_path": path,
            "k": meta["k"], "chunk_program": meta["chunk_program"],
            "log": lines.append, "chunk_text": {}, "commit_text": {}}, meta


@pytest.fixture(scope="module")
def recorded():
    lines = []
    ctx, meta = _ctx(FIXTURE, lines)
    assert os.path.getsize(ctx["xplane_path"]) < 1 << 20
    read = row_journey.analyse(ctx)
    got = row_journey.follow(ctx["program_trace"]["spans"], ctx["trace"],
                             ctx["chunk_program"])
    return ctx, read, got, meta, lines


def test_recorded_trace_is_from_the_chip_and_says_positions(recorded):
    ctx, _read, _got, meta, _lines = recorded
    assert ctx["trace"].n_device_planes >= 1
    assert meta["device"].startswith("TPU")
    spans = ctx["program_trace"]["spans"]
    stats = {name: set().union(*[s[3] for s in spans if s[0] == name])
             for name in ("ingest.admit", "ingest.host_stage",
                          "fused.stage_block", "fused.commit_staged",
                          "learner.dispatch")}
    assert "seq" in stats["ingest.admit"]
    assert {"seq_lo", "seq_hi", "through", "dropped"} \
        <= stats["ingest.host_stage"]
    assert {"block", "first", "through"} <= stats["fused.stage_block"]
    assert {"block", "through"} <= stats["fused.commit_staged"]
    assert {"chunk", "landed"} <= stats["learner.dispatch"]


def test_recorded_hops_are_non_negative_and_sum_to_the_journey(recorded):
    _ctx_, _read, got, _meta, _lines = recorded
    rows = got["rows"]
    assert rows.shape[0] >= 20 and rows.shape[1] == 5
    assert np.all(np.diff(rows, axis=1) >= 0)  # every hop of every add
    hops = row_journey.hops_ms(rows)
    total = sum(hops[h] for h in row_journey.HOPS)
    # to the nanosecond (the numbers are in ms)
    assert np.max(np.abs(total - hops["journey"])) < 1e-6
    assert np.all(hops["journey"] > 0)
    # a ticket is followed once
    assert len(set(got["seqs"])) == len(got["seqs"])


def test_recorded_adds_are_all_accounted_for(recorded):
    _ctx_, _read, got, meta, lines = recorded
    followed = got["rows"].shape[0]
    assert followed + got["dropped"] + got["on_the_way"] == got["admitted"]
    assert got["dropped"] == 0
    assert got["late_landed"] == 0  # position and time order agree
    # the window is ~20 chunks long: the adds of its last few cannot finish
    assert followed >= 0.5 * got["admitted"]
    mine = meta["row_journey"]
    assert followed == mine["followed"]
    for key in ("admitted", "dropped", "on_the_way", "pairs", "dispatches"):
        assert got[key] == mine[key], key
    assert any("followed to a chunk's end" in m for m in lines)
    assert any("outside every learner.run" in m for m in lines)


def test_recorded_match_from_the_end_pairs_every_dispatch_once(recorded):
    ctx, _read, got, meta, _lines = recorded
    spans, tr = ctx["program_trace"]["spans"], ctx["trace"]
    dispatches = [s for s in spans if s[0] == "learner.dispatch"]
    runs = row_journey.pair_from_the_end(dispatches, tr,
                                         ctx["chunk_program"])
    assert got["pairs"] == len(runs) == len(dispatches) >= meta["chunks"]
    paired = [runs[id(d)] for d in dispatches]
    assert len({r[0] for r in paired}) == len(paired)  # one execution each
    assert paired == sorted(paired)
    for d, (start, end) in zip(dispatches, paired):
        # host spans and device events lie on one clock, as the profiler
        # places them: a chunk starts on the device when the call that
        # dispatched it has begun, give or take the placement's error (in
        # this trace a chunk is 0.2 ms and one starts 0.25 ms "before" its
        # dispatch: the device's events lie that much early)
        assert d[1] - 0.5e-3 < start < end
    assert -0.5e-3 < got["dispatch_lead_s"] < 0
    # a chunk dispatched before the profiler started (its span missing, its
    # execution in the trace) does not shift the others' match
    later = row_journey.pair_from_the_end(dispatches[1:], tr,
                                          ctx["chunk_program"])
    assert all(later[id(d)] == runs[id(d)] for d in dispatches[1:])
    # while a match from the front would pair every one with its neighbour
    assert runs[id(dispatches[1])] != paired[0]


def test_recorded_numbers_are_those_the_live_run_read(recorded):
    _ctx_, read, _got, meta, _lines = recorded
    assert set(read) == set(row_journey.METRICS.values())
    for key, value in meta["row_journey"]["read"].items():
        assert read[key] == pytest.approx(value, rel=1e-9), key
        assert read[key] > 0, key
    # the tail's hops bound the journey's: p95 of a sum is at most the sum
    assert read["journey"] <= sum(read[h] for h in row_journey.HOPS)


def test_every_new_reader_reads_zero_over_a_program_that_says_nothing():
    """PR 25's fixture is what the parent's program writes: spans without
    tickets or positions. A listed metric cannot be left out of the line."""
    lines = []
    ctx, _meta = _ctx(OLD_FIXTURE, lines)
    names = [m["name"] for m in manifest.load()["per_layer"]
             if m["name"] in row_journey.METRICS]
    assert sorted(names) == sorted(row_journey.METRICS) and len(names) == 7
    for name in names:
        value = layer_reader(name)(ctx)
        assert value == 0.0 and type(value) is float, name
    assert sum("say no tickets and positions" in m for m in lines) == 1
    # the spans the two host metrics read are there; the gate is the stats
    spans = program_trace.analyse(ctx)["spans"]
    assert any(s[0] == "ingest.lock_wait" for s in spans)
    # and without a trace a reader has nothing to say
    assert layer_reader(names[0])({"log": print}) is None


def _span(name, start, end, **stats):
    return (name, float(start), float(end), stats)


def test_hand_made_followed_dropped_and_on_its_way():
    spans = [
        _span("ingest.admit", 1.0, 1.1, rows=4, seq=0),   # dropped
        _span("ingest.admit", 2.0, 2.1, rows=4, seq=1),   # followed
        _span("ingest.admit", 2.5, 2.6, rows=4, seq=2),   # same group as 1
        _span("ingest.admit", 9.0, 9.1, rows=4, seq=3),   # on its way
        _span("ingest.admit", 9.5, 9.6, rows=4),          # refused: no seq
        _span("ingest.host_stage", 1.2, 1.3, seq_lo=0, seq_hi=0, through=4,
              dropped=0),
        _span("ingest.host_stage", 2.7, 3.0, seq_lo=1, seq_hi=2, through=12,
              dropped=4),
        _span("ingest.host_stage", 9.2, 9.3, seq_lo=3, seq_hi=3, through=16,
              dropped=0),
        # the block starts past the dropped rows, before its group's span
        # has closed (the lock is released first)
        _span("fused.stage_block", 2.9, 3.5, block=7, first=5, through=12),
        _span("fused.commit_staged", 5.0, 5.1, block=7, through=12),
        _span("learner.dispatch", 0.5, 0.6, chunk=0, landed=0),
        _span("learner.dispatch", 5.2, 5.3, chunk=1, landed=12),
        _span("learner.dispatch", 8.0, 8.1, chunk=2, landed=12),
    ]
    tr = trace_reduce.Trace(
        window=(0.0, 20.0), op_names=[], op_start=np.zeros(0),
        op_end=np.zeros(0),
        mod_names=["jit_fn(1)", "jit_commit(2)", "jit_fn(1)", "jit_fn(1)",
                   "jit_fn(1)"],
        mod_start=np.asarray([0.1, 5.15, 0.7, 6.0, 8.2]),
        mod_end=np.asarray([0.4, 5.18, 5.9, 7.5, 9.0]),
        host=[], n_device_planes=1)
    got = row_journey.follow(spans, tr, "jit_fn")
    # four executions, three dispatches: matched from the end
    assert got["pairs"] == 3 and got["dispatches"] == 3
    assert got["admitted"] == 4 and got["seqs"] == [1, 2]
    assert (got["dropped"], got["on_the_way"]) == (1, 1)
    assert got["rows"].tolist() == [[2.0, 2.9, 2.9, 5.0, 7.5],
                                    [2.5, 2.9, 2.9, 5.0, 7.5]]
    assert got["dispatch_lead_s"] == pytest.approx(0.2)  # 0.7 - 0.5
    assert np.isnan(got["hook_at"]).all()  # no learner.on_chunk span here
    hops = row_journey.hops_ms(got["rows"])
    assert hops["queue"].tolist() == pytest.approx([900.0, 400.0])
    assert hops["staging"].tolist() == [0.0, 0.0]
    assert hops["land_to_done"].tolist() == pytest.approx([2500.0, 2500.0])
    assert hops["journey"].tolist() == pytest.approx([5500.0, 5000.0])
