"""The seven per-layer readers under ``setup_s`` (PR 52,
``benchmark/startup_phases.py``): on a recorded log the phases add up; over a
program that keeps no start-up log every reader gives the float 0.0 whatever
the cell put into ``ctx``; the clock offset, the end of set-up and the compile
pipeline's split on small logs written by hand."""

import itertools
import json
import os
import sys
import types

import pytest

from benchmark import manifest, run, startup_phases

MANIFEST = manifest.load()
FIXTURE = os.path.join(manifest.REPO, "benchmark", "fixtures",
                       "startup_phases", "fixture.json")
METRICS = startup_phases.METRICS


def fixture_ctx() -> tuple:
    with open(FIXTURE) as f:
        fx = json.load(f)
    ctx = {"trace": types.SimpleNamespace(window=tuple(fx["window"])),
           "startup_log": fx["log"], "startup_spans": fx["spans"]}
    return fx, ctx


def test_the_manifest_lists_the_seven_for_every_cell_under_setup_s():
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m["name"] for m in MANIFEST["per_layer"]][-7:] == list(METRICS)
    for name in METRICS:
        m = entries[name]
        assert (m["layer"], m["moves"], m["better"]) == (
            "start-up", "setup_s", "lower")
        assert "workloads" not in m  # start-up is in every cell
        assert m["source"] in ("program_span", "program_counter")


def test_on_the_recorded_log_the_phases_add_up_to_setup_less_unspanned():
    fx, ctx = fixture_ctx()
    got = startup_phases.analyse(ctx)
    assert got["end_found_by"] == "the window's first learner.run"
    phases = sum(s for s, _n in got["phases"].values())
    assert phases + got["setup_unspanned_s"] == pytest.approx(
        got["setup_s"], abs=1e-6)
    # the three phase metrics are among the phases; the rest is other phases
    assert got["import_s"] + got["backend_init_s"] \
        + got["first_dispatch_s"] <= phases + 1e-9
    assert 0 <= got["setup_unspanned_s"] < 0.25 * got["setup_s"]
    assert got["import_s"] > got["first_dispatch_s"] > 0
    # tracing and lowering lie inside the first dispatch and init, not beside
    assert 0 < got["trace_lower_s"] < got["setup_s"]
    # the two clocks: every pair gives the same offset to within 50 us
    assert len(got["offsets"]) >= 10
    assert max(got["offsets"]) - min(got["offsets"]) < 5e-5
    # what the recorder read then, this code reads now
    for name in METRICS:
        assert got[name] == pytest.approx(fx["read"][name], abs=1e-9), name
    assert got["setup_s"] == pytest.approx(fx["setup_s"], abs=1e-9)


@pytest.mark.parametrize("order", [METRICS, METRICS[::-1]],
                         ids=["listed", "reversed"])
def test_the_readers_give_the_same_numbers_in_any_order(order):
    fx, ctx = fixture_ctx()
    ctx["log"] = lambda _m: None
    got = {name: run.layer_reader(name)(ctx) for name in order}
    for name in METRICS:
        assert type(got[name]) is float
        assert got[name] == pytest.approx(fx["read"][name], abs=1e-9)


# -- over a program without the log ------------------------------------------

CELLS = {"static": "humanoid-mlp.learn-static",
         "ingest": "humanoid-mlp.learn-ingest",
         "torso": "humanoid-qwen3next-ep32.learn-static"}


class Watched(dict):
    """A ``ctx`` that notes which keys were asked for."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.asked = set()

    def __getitem__(self, key):
        self.asked.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        self.asked.add(key)
        return super().get(key, default)

    def __contains__(self, key):
        self.asked.add(key)
        return super().__contains__(key)


def cell_ctx(shape: str) -> Watched:
    """What ``run.py`` hands a reader in a traced run of each kind of cell:
    the drivers' keys (``learner.report``), ``run.py``'s own, and in the
    ingest cell what the readers before these have left there."""
    trace = types.SimpleNamespace(window=(10.0, 10.5))
    ctx = Watched(spans={"commit": [], "stage": []}, k=40, compile_s=2.5,
                  chunk_program="jit_fn", log=lambda _m: None, trace=trace,
                  peak={"flops": 1.0, "bytes": 1.0}, counts={})
    if shape == "ingest":
        ctx.update(spans={"commit": [0.001] * 75, "stage": [0.002] * 75},
                   admit_to_commit_s=[0.015] * 343,
                   program_trace={"spans": [("learner.run", 1.0, 2.0, {})],
                                  "commit": 0.0003, "commit_runs": 69},
                   row_journey={"queue": 1.0})
    if shape == "torso":
        ctx.update(k=1, chunk_program="jit_fn", torso_trace={"runs": 8})
    return ctx


def no_module(monkeypatch):
    import d4pg_tpu.obs

    monkeypatch.delattr(d4pg_tpu.obs, "startup_log")
    monkeypatch.setitem(sys.modules, "d4pg_tpu.obs.startup_log", None)


def no_names(monkeypatch):
    import d4pg_tpu.obs

    monkeypatch.setattr(d4pg_tpu.obs, "startup_log",
                        types.SimpleNamespace())


def no_entries(monkeypatch):
    from d4pg_tpu.obs import startup_log

    monkeypatch.setattr(startup_log, "LOG", startup_log.StartupLog())


ABSENT = {"ImportError": no_module, "AttributeError": no_names,
          "no entries": no_entries}


@pytest.mark.parametrize("metric, shape, absent", list(itertools.product(
    METRICS, CELLS, ABSENT)))
def test_over_a_program_without_the_log_a_reader_gives_the_float_zero(
        metric, shape, absent, monkeypatch, capfd):
    ABSENT[absent](monkeypatch)
    assert startup_phases.program_log() is None
    ctx = cell_ctx(shape)
    before = dict(ctx)
    value = run.layer_reader(metric)(ctx)
    assert value == 0.0 and type(value) is float
    # at once: nothing of ctx read but whether there is a trace (and this
    # module's own keys), no key of the drivers' taken or changed
    assert ctx.asked <= {"trace", startup_phases.KEY, "startup_log",
                         "startup_spans"}
    assert {k: v for k, v in ctx.items() if k != startup_phases.KEY} \
        == before
    # said once, however many of the seven run after it
    for other in METRICS:
        assert run.layer_reader(other)(ctx) == 0.0
    err = capfd.readouterr().err
    assert err.count("keeps no start-up log") == 1
    # and the line is one manifest.validate_line takes
    cell = CELLS[shape]
    want = manifest.metrics_for(MANIFEST, cell, True)
    metrics = {name: {"value": 1.0, "unit": m["unit"]}
               for name, m in want.items()}
    metrics[metric] = {"value": value, "unit": want[metric]["unit"]}
    obj = {"correct": True, "attempted": 8, "failed": 0, "metrics": metrics,
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 1 << 30, "busy_s": 0.4,
                      "window_s": 0.5}}
    manifest.validate_line(MANIFEST, cell, True, obj)


@pytest.mark.parametrize("metric", METRICS)
def test_with_no_trace_at_all_a_reader_returns_nothing(metric):
    assert run.layer_reader(metric)({"log": lambda _m: None}) is None


# -- small logs written by hand -----------------------------------------------

MAIN, OTHER = 1, 2


def entry(name, t0, t1, thread=MAIN, parent=-1, phase=False, **stats):
    return (name, t0, t1, thread, parent, stats, phase)


def snap_of(entries, epoch=100.0, overflow=0):
    return {"epoch": epoch, "bound": 4096, "overflow": overflow,
            "entries": entries}


def test_the_union_counts_nested_and_overlapping_intervals_once():
    assert startup_phases.union_s([]) == 0.0
    assert startup_phases.union_s([(0, 4), (1, 2), (3, 5), (7, 8)]) == 6.0
    assert startup_phases.union_s([(3, 5), (0, 1)]) == 3.0


def test_the_offset_pairs_spans_by_name_and_chunk_number():
    entries = [entry("learner.dispatch", 105.0, 105.1, chunk=3),
               entry("learner.dispatch", 106.0, 106.1, chunk=4),
               entry("learner.chunk", 104.9, 105.2, chunk=3),
               entry("learner.dispatch", 101.0, 101.1, chunk=0),
               entry("learner.run", 104.8, 107.0, n=80)]
    spans = [("learner.dispatch", 5.25, 5.35, {"chunk": "3"}),
             ("learner.dispatch", 6.25, 6.35, {"chunk": 4}),
             ("learner.chunk", 5.15, 5.45, {"chunk": 3}),
             ("learner.run", 5.05, 7.25, {"n": 80}),
             ("ingest.admit", 5.0, 5.1, {"seq": 3})]
    offsets = startup_phases.clock_offset(entries, spans)
    assert offsets == pytest.approx([99.75, 99.75, 99.75])


def setup_log():
    """Epoch 100; imports 100-103 (jax 2, the remainder 1), backend 103-105,
    a second of nothing, a first dispatch 106-110 with trace 1.5 (one trace
    nested in another), lower 1, backend 1 (a miss), and a commit program
    compiled on another thread with the cache off; the window's first run
    at 112."""
    return [
        entry("import.jax", 100.0, 102.0, phase=True),
        entry("import.d4pg_tpu", 102.0, 103.0, phase=True, aiohttp=0.6),
        entry("startup.backend", 103.0, 105.0, phase=True),
        entry("learner.run", 105.9, 110.5, n=40),
        entry("learner.first_dispatch", 106.0, 110.0, parent=3, phase=True,
              program="learner.chunk"),
        entry("compile.trace", 106.2, 106.7, parent=4, fun_name="inner"),
        entry("compile.trace", 106.0, 107.5, parent=4, fun_name="fn"),
        entry("compile.lower", 107.5, 108.5, parent=4, fun_name="fn"),
        entry("cache.request", 108.6, 108.6, parent=4),
        entry("compile.backend", 108.5, 109.5, parent=4, fun_name="jit(fn)"),
        entry("compile.backend", 109.0, 109.4, thread=OTHER,
              fun_name="jit(commit)"),
        entry("cache.request", 109.6, 109.6, thread=OTHER),
        entry("cache.hit", 109.6, 109.6, thread=OTHER),
        entry("cache.load", 109.6, 109.9, thread=OTHER),
        entry("compile.backend", 109.5, 110.0, thread=OTHER,
              fun_name="jit(warm)"),
        entry("learner.dispatch", 110.1, 110.2, parent=3, chunk=0),
        entry("learner.run", 112.0, 113.0, n=40),
        entry("learner.dispatch", 112.1, 112.2, parent=16, chunk=1),
        entry("compile.trace", 112.3, 112.9, parent=16, fun_name="late"),
        entry("learner.run", 113.0, None, n=40),
    ]


def test_a_small_log_reduces_to_the_seven_numbers():
    spans = [("learner.dispatch", 12.1, 12.2, {"chunk": 1})]
    got = startup_phases.reduce(snap_of(setup_log()), spans, (11.9, 14.0))
    assert got["end_found_by"] == "the window's first learner.run"
    assert got["setup_s"] == pytest.approx(12.0)
    assert got["import_s"] == pytest.approx(3.0)
    assert got["backend_init_s"] == pytest.approx(2.0)
    assert got["first_dispatch_s"] == pytest.approx(4.0)
    # the nested trace is counted once; the one in the window not at all
    assert got["trace_lower_s"] == pytest.approx(2.5)
    assert got["cache_load_s"] == pytest.approx(0.3)
    assert got["cache_miss_programs"] == 1.0
    assert got["compiled"] == [("jit(fn)", "miss"),
                               ("jit(commit)", "uncached"),
                               ("jit(warm)", "hit")]
    assert got["setup_unspanned_s"] == pytest.approx(12.0 - 9.0)
    (program, took, trace, lower, backend, own), = got["first_dispatch"]
    assert (program, took) == ("learner.chunk", pytest.approx(4.0))
    assert (trace, lower, backend) == (pytest.approx(1.5),
                                       pytest.approx(1.0),
                                       pytest.approx(1.0))
    assert own == pytest.approx(0.5)
    assert got["rest"] == {"aiohttp": 0.6}
    startup_phases.report(snap_of(setup_log()), got)  # prints, raises nothing


def test_a_log_that_was_full_before_the_window_says_so_and_reads_short():
    entries = [e for e in setup_log() if e[1] < 112.0]
    spans = [("learner.dispatch", 12.1, 12.2, {"chunk": 1})]
    got = startup_phases.reduce(snap_of(entries, overflow=500), spans,
                                (11.9, 14.0))
    assert "the last entry the log has" in got["end_found_by"]
    assert got["setup_s"] == pytest.approx(10.5)  # the last entry's end
    assert got["import_s"] == pytest.approx(3.0)
    assert got["setup_unspanned_s"] == pytest.approx(1.5)
    assert got["offsets"] == []
