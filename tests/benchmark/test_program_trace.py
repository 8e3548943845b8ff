"""``benchmark/program_trace.py``: the HLO-text parser on a hand-made module,
and the whole reduction on a trace recorded on the v5e by PR 25's chip runs
(``benchmark/tools/record_program_fixture.py``: the ingest cell at rehearsal
size, four calls of five chunks, with the compiled text of the chunk and
commit programs beside it; ``benchmark/fixtures/program_trace/``, a directory
of its own so that the PR 24 fixture stays the only one its test globs)."""

import gzip
import json
import os

import numpy as np
import pytest

from benchmark import manifest, program_trace, trace_reduce

FIXTURE = os.path.join(manifest.REPO, "benchmark", "fixtures",
                       "program_trace")

HLO = '''HloModule jit_fn, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(fn)/while/body/replay.gather/mul"}
}

%inner_body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %bare.1 = (s32[], f32[8]{0}) tuple(%t)
  ROOT %descend.1 = (s32[], f32[8]{0}) tuple(%t), metadata={op_name="jit(fn)/while/body/replay.sample/while/body/add"}
}

%inner_cond (t: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.9 = pred[] constant(true)
}

%body (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  %while.7 = (s32[], f32[8]{0}) while(%c), condition=%inner_cond, body=%inner_body, metadata={op_name="jit(fn)/while/body/replay.sample/while"}
  %fusion.3 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(fn)/while/body/replay.gather/mul"}
  %fusion.4 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(fn)/while/body/learner.update/update.critic/transpose(learner.update)/update.critic/jvp(Critic)/dot"}
  %fusion.5 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(fn)/while/body/learner.update/sub"}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%c)
}

%cond (c: (s32[], f32[8])) -> pred[] {
  %c.1 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %copy.33 = f32[8]{0} copy(%x), metadata={op_name="jit(fn)/while/body/replay.gather/convert_element_type"}
  %while.2 = (s32[], f32[8]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(fn)/while"}
  ROOT %out = f32[8]{0} get-tuple-element(%while.2), index=1
}
'''


def test_parser_splits_loop_from_prologue_and_reads_scopes():
    prog = program_trace.parse_program(HLO)
    # hoisted above the scan: carries the gather's scope, runs outside
    assert not prog["copy.33"].in_loop
    assert program_trace.innermost(prog["copy.33"].op_name,
                                   program_trace.TOP_SCOPES) == "replay.gather"
    assert prog["fusion.3"].in_loop and not prog["fusion.3"].container
    # containers: the scan itself, and a loop nested in its body
    assert prog["while.2"].container and not prog["while.2"].in_loop
    assert prog["while.7"].container and prog["while.7"].in_loop
    assert prog["descend.1"].in_loop  # reached through the nested loop
    # the compiler's own instruction inside a nested loop takes the loop's
    # scope (a gather expanded into one-row updates carries no metadata)
    assert prog["bare.1"].in_loop and program_trace.innermost(
        prog["bare.1"].op_name, program_trace.TOP_SCOPES) == "replay.sample"
    scopes = program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES
    # the innermost scope is the last in the path, backward ops included
    assert program_trace.innermost(prog["fusion.4"].op_name,
                                   scopes) == "update.critic"
    assert program_trace.innermost(prog["fusion.5"].op_name,
                                   scopes) == "learner.update"
    assert program_trace.innermost(prog["tuple.2"].op_name, scopes) is None
    assert program_trace.instruction_of(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %c), kind=kLoop") == "fusion.3"
    assert program_trace.instruction_of("fusion.3") == "fusion.3"


def test_scope_times_on_hand_made_events():
    prog = program_trace.parse_program(HLO)
    ops = [("%copy.33 = f32[8] copy(...)", 0.0, 1.0),       # prologue
           ("%while.2 = (...) while(...)", 1.0, 9.0),        # container
           ("%fusion.3 = f32[8] fusion(...)", 1.0, 2.0),     # gather
           ("%while.7 = (...) while(...)", 2.0, 4.0),        # container
           ("%descend.1 = (...) tuple(...)", 2.0, 4.0),      # sample
           ("%fusion.4 = f32[8] fusion(...)", 4.0, 7.0),     # update.critic
           ("%fusion.5 = f32[8] fusion(...)", 7.0, 7.5),     # update's own
           ("%tuple.2 = (...) tuple(...)", 7.5, 8.0),        # loop, no scope
           ("%fusion.3 = f32[8] fusion(...)", 20.0, 21.0)]   # another program
    tr = trace_reduce.Trace(
        window=(0.0, 30.0), op_names=[o[0] for o in ops],
        op_start=np.asarray([o[1] for o in ops]),
        op_end=np.asarray([o[2] for o in ops]),
        mod_names=["jit_fn(1)", "jit_commit(2)"],
        mod_start=np.asarray([0.0, 20.0]), mod_end=np.asarray([10.0, 21.0]),
        host=[], n_device_planes=1)
    got = program_trace.scope_times(
        tr, "jit_fn", prog,
        program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES)
    assert got["runs"] == 1 and got["total"].tolist() == [10.0]
    assert got["outside"]["replay.gather"].tolist() == [1.0]
    assert got["loop"]["replay.gather"].tolist() == [1.0]
    assert got["loop"]["replay.sample"].tolist() == [2.0]
    assert got["loop"]["update.critic"].tolist() == [3.0]
    assert got["loop"]["learner.update"].tolist() == [0.5]
    assert got["loop"][""].tolist() == [0.5]
    assert got["ops"].tolist() == [8.0]  # containers are not counted twice


@pytest.fixture(scope="module")
def recorded():
    def text(name):
        with gzip.open(os.path.join(FIXTURE, name), "rt") as f:
            return f.read()

    with open(os.path.join(FIXTURE, "fixture.json")) as f:
        meta = json.load(f)
    path = os.path.join(FIXTURE, "ingest-rehearsal.xplane.pb.gz")
    assert os.path.getsize(path) < 1 << 20
    lines = []
    ctx = {"trace": trace_reduce.load(path), "xplane_path": path,
           "k": meta["k"], "chunk_program": meta["chunk_program"],
           "log": lines.append,
           "chunk_text": program_trace.parse_program(text("chunk.hlo.txt.gz")),
           "commit_text": program_trace.parse_program(
               text("commit.hlo.txt.gz"))}
    return ctx, program_trace.analyse(ctx), meta, lines


def test_recorded_trace_is_from_the_chip(recorded):
    ctx, _read, meta, _lines = recorded
    assert ctx["trace"].n_device_planes >= 1
    assert meta["device"].startswith("TPU")


def test_recorded_event_names_are_instructions_of_the_compiled_text(recorded):
    ctx, _read, _meta, _lines = recorded
    tr = ctx["trace"]
    start, end = trace_reduce.program_runs(tr, ctx["chunk_program"])
    inside = (tr.op_start >= start[0]) & (tr.op_end <= end[0])
    names = {program_trace.instruction_of(tr.op_names[i])
             for i in np.flatnonzero(inside)}
    assert len(names) > 50
    assert names <= set(ctx["chunk_text"])


def test_recorded_chunk_numbers_sum_to_the_chunk_time(recorded):
    ctx, read, meta, _lines = recorded
    start, end = trace_reduce.program_runs(ctx["trace"], ctx["chunk_program"])
    assert start.size == meta["chunks"] == 20  # four calls of five
    chunk = float(np.median(end - start))
    assert read["chunk_total"] == chunk
    named = meta["k"] * sum(read[s] for s in program_trace.TOP_SCOPES) \
        + read["prologue"]
    assert all(read[s] > 0 for s in program_trace.TOP_SCOPES)
    # At the cells' sizes the five numbers are 98.5-99.9 % of the chunk
    # (PERF.md section 5). This chunk is 0.3 ms of ~1 us operations: the
    # gaps between them are 12 % of it and the loop's own bookkeeping 7 %,
    # so here the five are held to four fifths, and to the whole within 5 %
    # once the two remainders the reader prints are added.
    assert named > 0.8 * chunk
    assert named + read["loop_unscoped"] + read["not_running"] \
        == pytest.approx(chunk, rel=0.05)
    # the live run read the same numbers from the same trace
    for key, value in meta["read"].items():
        assert read[key] == pytest.approx(value, rel=1e-9), key


def test_recorded_prologue_finds_an_operation_outside_the_while(recorded):
    ctx, read, _meta, _lines = recorded
    assert read["prologue"] > 0
    outside = {n for n, ins in ctx["chunk_text"].items()
               if not ins.in_loop and not ins.container}
    seen = {program_trace.instruction_of(n) for n in ctx["trace"].op_names}
    # among them the hoisted whole-ring casts (`copy` of `storage_obs`), which
    # the compiler made and which therefore carry no scope
    hoisted = [n for n in ctx["trace"].op_names
               if program_trace.instruction_of(n) in outside
               and "copy(" in n and "storage_" in n]
    assert outside & seen and hoisted


def test_recorded_commit_runs_between_chunks_with_both_scopes(recorded):
    ctx, read, _meta, lines = recorded
    tr = ctx["trace"]
    cs, ce = trace_reduce.program_runs(tr, program_trace.COMMIT_PROGRAM)
    s, e = trace_reduce.program_runs(tr, ctx["chunk_program"])
    assert cs.size == read["commit_runs"] >= 3 and read["commit"] > 0
    between = 0
    for a, b in zip(cs, ce):  # never during a chunk program, mostly between
        i = np.searchsorted(s, a)
        assert i == 0 or e[i - 1] <= a
        assert i == s.size or b <= s[i]
        between += 0 < i < s.size
    assert between >= 3
    split = program_trace.scope_times(tr, program_trace.COMMIT_PROGRAM,
                                      ctx["commit_text"],
                                      program_trace.COMMIT_SCOPES)
    for scope in program_trace.COMMIT_SCOPES:
        assert np.all(split["loop"][scope] + split["outside"][scope] > 0)
    assert any("commit program" in m for m in lines)


def test_recorded_span_stats_are_read(recorded):
    ctx, read, meta, _lines = recorded
    spans, window = read["spans"], ctx["trace"].window
    names = {s[0] for s in spans}
    assert {"learner.run", "learner.chunk", "learner.dispatch",
            "learner.on_chunk", "ingest.commit", "ingest.stage",
            "ingest.lock_wait", "fused.stage_block", "fused.h2d",
            "fused.commit_staged", "ingest.admit",
            "ingest.host_stage"} <= names
    wait = program_trace.span_stat(spans, "fused.stage_block", "wait_ms",
                                   window)
    flight = program_trace.span_stat(spans, "fused.commit_staged",
                                     "inflight_ms", window)
    assert wait.size >= 3 and flight.size >= 3
    assert read["staging_wait"] == float(np.median(wait)) > 0
    assert read["inflight"] == float(np.median(flight)) > 0
    # a block keeps its id from stage to commit
    staged = program_trace.span_stat(spans, "fused.stage_block", "block",
                                     window)
    landed = program_trace.span_stat(spans, "fused.commit_staged", "block",
                                     window)
    assert set(landed[1:]) <= set(staged)
    chunks = program_trace.span_stat(spans, "learner.chunk", "chunk", window)
    assert chunks.size == meta["chunks"] and np.all(np.diff(chunks) == 1)


def test_recorded_idle_gaps_go_to_the_innermost_program_span(recorded):
    ctx, read, _meta, _lines = recorded
    gaps = program_trace.idle_by_span(ctx["trace"], read["spans"], n=100)
    busy, window = trace_reduce.busy_and_window(ctx["trace"])
    assert sum(g[1] for g in gaps) == pytest.approx(window - busy, rel=1e-6)
    owners = {g[0] for g in gaps}
    assert owners & {"learner.dispatch", "learner.on_chunk", "fused.h2d",
                     "learner.chunk", "fused.commit_staged"}
    assert not any(o.startswith("bench.") for o in owners)


def test_a_program_without_table_or_spans_reads_zero_not_nothing():
    """The parent of PR 25 under these files: no table, no spans. The line
    needs a value for every listed metric, so nothing-to-read is 0.0."""
    tr = trace_reduce.Trace(
        window=(0.0, 10.0), op_names=["%fusion.1 = f32[] fusion()"],
        op_start=np.asarray([1.0]), op_end=np.asarray([2.0]),
        mod_names=["jit_fn(1)"], mod_start=np.asarray([1.0]),
        mod_end=np.asarray([2.0]), host=[], n_device_planes=1)
    path = os.path.join(FIXTURE, "ingest-rehearsal.xplane.pb.gz")
    lines = []
    ctx = {"trace": tr, "xplane_path": path, "k": 4, "chunk_program": "jit_fn",
           "log": lines.append, "chunk_text": {}, "commit_text": {}}
    read = program_trace.analyse(ctx)
    assert [read[s] for s in program_trace.TOP_SCOPES] == [0.0] * 4
    assert read["commit"] == 0.0 and read["commit_runs"] == 0
    assert read["prologue"] == 0.0  # no text, so no loop to be outside of
    assert program_trace.read_scope(ctx, "replay.sample", 1e6) == 0.0
    assert program_trace.read_scope({"log": print}, "commit", 1e3) is None
