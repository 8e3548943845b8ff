"""The start-up log (PR 52, ``d4pg_tpu/obs/startup_log.py``): its bound and
``overflow``, nesting by thread, which entries are phases, first imports by
self time, the compile pipeline's events as ``startup.configure`` forwards
them, ``obs.trace.span`` feeding it with and without an annotator, and the
fused loop's first dispatch. CPU, tiny sizes, private logs."""

import builtins
import os
import sys
import threading
import time

import numpy as np
import pytest

from d4pg_tpu import startup
from d4pg_tpu.obs import startup_log, trace
from d4pg_tpu.obs.startup_log import StartupLog

pytestmark = pytest.mark.obs


@pytest.fixture
def log(monkeypatch):
    """A private log in the process-wide one's place, for ``span()``,
    ``startup``'s listeners and the loop."""
    log = StartupLog()
    monkeypatch.setattr(trace, "_LOG", log)
    monkeypatch.setattr(startup, "LOG", log)
    monkeypatch.setattr(startup_log, "LOG", log)
    return log


def names(log):
    return [e[0] for e in log.snapshot()["entries"]]


def test_the_bound_holds_and_everything_past_it_is_counted():
    log = StartupLog(bound=4)
    for i in range(6):
        log.end(log.begin(f"span.{i}", {}))
    log.add("compile.trace", 0.5)
    snap = log.snapshot()
    assert [e[0] for e in snap["entries"]] == [f"span.{i}" for i in range(4)]
    assert log.full and snap["overflow"] == 3 and snap["bound"] == 4
    # an entry that began under the bound still closes past it
    assert all(e[2] is not None and e[2] >= e[1] for e in snap["entries"])


def test_the_epoch_comes_first_and_entries_lie_on_the_monotonic_clock():
    before = time.monotonic()
    log = StartupLog()
    index = log.begin("learner.run", {"n": 3})
    log.annotate(index, {"rows": 7})
    log.end(index)
    (name, t0, t1, thread, parent, stats, phase), = log.snapshot()["entries"]
    assert before <= log.epoch <= t0 <= t1 <= time.monotonic()
    assert (name, parent, stats, phase) == (
        "learner.run", -1, {"n": 3, "rows": 7}, False)
    assert thread == threading.get_ident()


def test_entries_nest_by_thread():
    log = StartupLog()
    outer = log.begin("learner.run", {})
    seen = {}

    def other():
        a = log.begin("ingest.host_stage", {})
        b = log.begin("ingest.lock_wait", {})
        log.end(b)
        log.end(a)
        seen["a"], seen["b"] = a, b

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    inner = log.begin("learner.chunk", {})
    log.add("compile.trace", 0.01, fun_name="jit(fn)")
    log.end(inner)
    log.end(outer)
    entries = log.snapshot()["entries"]
    parent = {i: e[4] for i, e in enumerate(entries)}
    assert parent[outer] == -1 and parent[inner] == outer
    # the other thread's spans hang from each other, not from the main's
    assert parent[seen["a"]] == -1 and parent[seen["b"]] == seen["a"]
    event = [e for e in entries if e[0] == "compile.trace"][0]
    assert event[4] == inner and event[5] == {"fun_name": "jit(fn)"}
    assert event[2] - event[1] == pytest.approx(0.01)
    assert {e[3] for e in entries} == {threading.get_ident(), entries[
        seen["a"]][3]}


@pytest.mark.parametrize("name", sorted(startup_log.PHASES))
def test_a_phase_is_a_named_span_of_the_main_thread_outside_any_other(name):
    log = StartupLog()
    plain = log.begin("learner.run", {})  # not a phase: phases lie beneath
    first = log.begin(name, {})
    nested = log.begin("ring.relayout", {})
    log.end(nested)
    log.end(first)
    again = log.begin(name, {})
    log.end(again)
    log.end(plain)
    off = {}

    def other():
        off["i"] = log.begin(name, {})
        log.end(off["i"])

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    phase = [e[6] for e in log.snapshot()["entries"]]
    assert phase[plain] is False
    assert phase[first] is True and phase[again] is True
    assert phase[nested] is False and phase[off["i"]] is False


def toy_packages(tmp_path, monkeypatch):
    """``toy_outer`` (0.05 s of its own) imports ``toy_inner`` (0.1 s)."""
    for pkg, body in (
            ("toy_outer", "import time\ntime.sleep(0.05)\n"
                          "import toy_inner\nfrom toy_inner import leaf\n"),
            ("toy_inner", "import time\ntime.sleep(0.1)\n")):
        os.makedirs(tmp_path / pkg)
        (tmp_path / pkg / "__init__.py").write_text(body)
    (tmp_path / "toy_inner" / "leaf.py").write_text(
        "import time\ntime.sleep(0.02)\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    for name in ("toy_outer", "toy_inner", "toy_inner.leaf"):
        monkeypatch.delitem(sys.modules, name, raising=False)


def test_first_imports_are_booked_by_self_time_and_package(
        tmp_path, monkeypatch):
    toy_packages(tmp_path, monkeypatch)
    monkeypatch.setattr(startup_log, "NAMED",
                        frozenset({"toy_outer", "toy_inner"}))
    log = StartupLog()
    before = builtins.__import__
    log.watch_imports()
    try:
        assert builtins.__import__ == log._timed_import
        t0 = time.monotonic()
        import toy_outer  # noqa: F401
        took = time.monotonic() - t0
        import toy_outer  # noqa: F401,F811 - found in sys.modules: no entry
        import json  # noqa: F401
    finally:
        log.unwatch_imports()
    assert builtins.__import__ is before
    entries = log.snapshot()["entries"]
    by_name = {e[0]: e for e in entries}
    assert set(by_name) <= {"import.toy_outer", "import.toy_inner",
                            "import.d4pg_tpu"}
    outer, inner = by_name["import.toy_outer"], by_name["import.toy_inner"]
    # what toy_outer imports of toy_inner for the first time is toy_inner's
    assert 0.05 <= outer[2] - outer[1] < 0.09
    assert 0.12 <= inner[2] - inner[1] < 0.16
    # consecutive, phases, and their lengths add up to the statement's
    assert all(e[6] for e in entries)
    assert all(a[2] == pytest.approx(b[1]) for a, b in zip(entries,
                                                           entries[1:]))
    assert sum(e[2] - e[1] for e in entries) == pytest.approx(took, abs=5e-3)


def test_small_packages_go_to_the_one_remainder_which_names_its_parts(
        tmp_path, monkeypatch):
    toy_packages(tmp_path, monkeypatch)
    log = StartupLog()
    log.watch_imports()
    try:
        import toy_outer  # noqa: F401
    finally:
        log.unwatch_imports()
    (name, t0, t1, _th, parent, stats, phase), = log.snapshot()["entries"]
    assert (name, parent, phase) == ("import.d4pg_tpu", -1, True)
    assert t1 - t0 >= 0.17
    assert stats["toy_inner"] == pytest.approx(0.12, abs=0.03)
    assert stats["toy_outer"] == pytest.approx(0.05, abs=0.03)


def test_an_import_inside_a_phase_is_that_phases(tmp_path, monkeypatch):
    toy_packages(tmp_path, monkeypatch)
    log = StartupLog()
    log.watch_imports()
    try:
        index = log.begin("startup.backend", {})
        import toy_inner  # noqa: F401
        log.end(index)
    finally:
        log.unwatch_imports()
    entries = log.snapshot()["entries"]
    assert [(e[0], e[4], e[6]) for e in entries] == [
        ("startup.backend", -1, True), ("import.d4pg_tpu", index, False)]


def test_the_hook_comes_out_when_the_log_is_full(tmp_path, monkeypatch):
    toy_packages(tmp_path, monkeypatch)
    log = StartupLog(bound=1)
    before = builtins.__import__
    log.watch_imports()
    try:
        log.end(log.begin("learner.run", {}))
        assert builtins.__import__ == log._timed_import
        log.end(log.begin("learner.run", {}))  # past the bound
        assert builtins.__import__ is before
        import toy_outer  # noqa: F401
    finally:
        log.unwatch_imports()
    assert names(log) == ["learner.run"] and log.overflow == 1


def test_a_hook_put_in_later_keeps_working_when_this_one_leaves(
        tmp_path, monkeypatch):
    toy_packages(tmp_path, monkeypatch)
    log = StartupLog()
    before = builtins.__import__
    log.watch_imports()
    ours = builtins.__import__
    seen = []

    def later(name, *args, **kwargs):
        seen.append(name)
        return ours(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", later)
    log.unwatch_imports()  # not on top: stays behind, a bare pass-through
    import toy_outer  # noqa: F401
    monkeypatch.setattr(builtins, "__import__", before)
    assert "toy_outer" in seen and names(log) == []


def test_the_compile_pipelines_events_land_under_the_open_span(log):
    from jax._src import compiler, dispatch

    # the names as this jax spells them, read off its source
    assert {dispatch.JAXPR_TRACE_EVENT, dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
            dispatch.BACKEND_COMPILE_EVENT} <= set(startup._DURATIONS)
    text = open(compiler.__file__).read()
    for event in list(startup._EVENTS) + [
            "/jax/compilation_cache/cache_retrieval_time_sec"]:
        assert f"'{event}'" in text or f'"{event}"' in text
    with trace.span("learner.first_dispatch", program="learner.chunk"):
        startup._on_duration(dispatch.JAXPR_TRACE_EVENT, 0.3,
                             fun_name="fn")
        startup._on_event("/jax/compilation_cache/compile_requests_use_cache")
        startup._on_event("/jax/compilation_cache/cache_hits")
        startup._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.1)
        startup._on_duration(dispatch.BACKEND_COMPILE_EVENT, 0.2,
                             fun_name="jit(fn)")
        startup._on_duration("/jax/some/other_duration", 9.0)
        startup._on_event("/jax/some/other_event")
    entries = log.snapshot()["entries"]
    assert [e[0] for e in entries] == [
        "learner.first_dispatch", "compile.trace", "cache.request",
        "cache.hit", "cache.load", "compile.backend"]
    assert entries[0][6] and entries[0][5] == {"program": "learner.chunk"}
    assert all(e[4] == 0 and not e[6] for e in entries[1:])
    assert entries[-1][5] == {"fun_name": "jit(fn)"}
    assert startup_log.compiled(entries) == [("jit(fn)", "hit")]


def test_a_real_compile_reports_through_the_listeners(log, monkeypatch):
    import jax
    import jax.numpy as jnp

    jax.monitoring.register_event_duration_secs_listener(startup._on_duration)
    jax.monitoring.register_scalar_listener(startup._on_scalar)
    try:
        with trace.span("learner.first_dispatch", program="toy"):
            jax.jit(lambda x: jnp.sin(x) * 3.25)(jnp.arange(7.0))
    finally:
        jax.monitoring.unregister_event_duration_listener(
            startup._on_duration)
        jax.monitoring.unregister_scalar_listener(startup._on_scalar)
    got = names(log)
    assert {"compile.trace", "compile.lower", "compile.backend"} <= set(got)
    backend = [e for e in log.snapshot()["entries"]
               if e[0] == "compile.backend"][-1]
    assert backend[5]["fun_name"].startswith("jit(") and backend[4] == 0


def test_only_the_outermost_trace_of_a_thread_is_kept(log):
    """jax reports a jitted function traced inside another, innermost
    first, and announces each trace's start by a scalar of the event's
    name: the one that ends with none open on its thread is kept."""
    trace_event = "/jax/core/compile/jaxpr_trace_duration"
    lower_event = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    assert {trace_event, lower_event} == startup._NESTING
    startup._on_scalar(trace_event, 1.0, fun_name="outer")
    startup._on_scalar(trace_event, 1.1, fun_name="inner")
    startup._on_duration(trace_event, 0.001, fun_name="inner")
    startup._on_scalar("/jax/core/compile/backend_compile_duration", 1.2)
    t = threading.Thread(target=lambda: (
        startup._on_scalar(trace_event, 1.2, fun_name="other"),
        startup._on_duration(trace_event, 0.002, fun_name="other")))
    t.start()
    t.join(timeout=10)
    startup._on_scalar(trace_event, 1.3, fun_name="inner")
    startup._on_duration(trace_event, 0.001, fun_name="inner")
    startup._on_duration(trace_event, 0.03, fun_name="outer")
    # a kernel's lowering rule traces its helpers: the lowering's seconds
    startup._on_scalar(lower_event, 2.0, fun_name="outer")
    startup._on_scalar(trace_event, 2.1, fun_name="add")
    startup._on_duration(trace_event, 0.0001, fun_name="add")
    startup._on_duration(lower_event, 0.004, fun_name="outer")
    entries = log.snapshot()["entries"]
    assert [(e[0], e[5]["fun_name"]) for e in entries] == [
        ("compile.trace", "other"), ("compile.trace", "outer"),
        ("compile.lower", "outer")]
    assert entries[1][2] - entries[1][1] == pytest.approx(0.03)


def test_a_jitted_function_traced_inside_another_leaves_one_entry(log):
    import jax
    import jax.numpy as jnp

    listeners = ((jax.monitoring.register_event_duration_secs_listener,
                  jax.monitoring.unregister_event_duration_listener,
                  startup._on_duration),
                 (jax.monitoring.register_scalar_listener,
                  jax.monitoring.unregister_scalar_listener,
                  startup._on_scalar))
    for register, _un, fn in listeners:
        register(fn)
    try:
        inner = jax.jit(lambda x: jnp.tanh(x) * 1.75)
        outer = jax.jit(lambda x: inner(inner(x) + 0.5) - inner(x * 2.25))
        outer(jnp.arange(5.0))
    finally:
        for _reg, unregister, fn in listeners:
            unregister(fn)
    traces = [e for e in log.snapshot()["entries"] if e[0] == "compile.trace"]
    # the eager arange may trace a program of its own; the jitted call is one
    assert 1 <= len(traces) <= 2
    assert sum("lambda" in e[5]["fun_name"] for e in traces) == 1


@pytest.mark.parametrize("events, kind", [
    (("cache.request", "cache.hit", "cache.load"), "hit"),
    (("cache.request",), "miss"),
    ((), "uncached"),
])
def test_a_compile_is_a_hit_a_miss_or_outside_the_cache(events, kind):
    log = StartupLog()
    log.add("cache.request")
    log.add("cache.hit")
    log.add("compile.backend", 0.001, fun_name="jit(before)")
    for name in events:
        log.add(name)
    log.add("compile.backend", 0.001, fun_name="jit(this)")
    assert startup_log.compiled(log.snapshot()["entries"]) == [
        ("jit(before)", "hit"), ("jit(this)", kind)]


def test_span_keeps_entries_with_no_annotator_installed(log):
    assert trace._annotator is None
    with trace.span("learner.flush", chunk=3) as flush:
        flush.set_metadata(rows=16)
        with trace.span("fused.h2d"):
            pass
    entries = log.snapshot()["entries"]
    assert [(e[0], e[4], e[5]) for e in entries] == [
        ("learner.flush", -1, {"chunk": 3, "rows": 16}),
        ("fused.h2d", 0, {})]
    assert all(e[2] is not None for e in entries)


def test_span_feeds_the_annotator_and_the_log_alike(log):
    class Annotation:
        made = []

        def __init__(self, name, **stats):
            self.name, self.stats, self.open = name, dict(stats), None
            self.made.append(self)

        def __enter__(self):
            self.open = True
            return self

        def __exit__(self, *exc):
            self.open = False

        def set_metadata(self, **stats):
            self.stats.update(stats)

    trace.set_annotator(Annotation)
    try:
        with trace.span("fused.stage_block", block=2) as sp:
            sp.set_metadata(wait_ms=1.5)
            assert Annotation.made[0].open is True
    finally:
        trace.set_annotator(None)
    (ann,) = Annotation.made
    assert (ann.name, ann.stats, ann.open) == (
        "fused.stage_block", {"block": 2, "wait_ms": 1.5}, False)
    (entry,) = log.snapshot()["entries"]
    assert entry[0] == "fused.stage_block" and entry[5] == ann.stats


def test_once_the_log_is_full_span_is_what_it_was_before(monkeypatch):
    log = StartupLog(bound=1)
    monkeypatch.setattr(trace, "_LOG", log)
    with trace.span("learner.run"):
        pass
    assert not log.full
    with trace.span("learner.run"):  # the first past the bound fills it
        pass
    assert log.full and log.overflow == 1
    assert trace.span("learner.run", n=1) is trace.NULL_SPAN
    sentinel = object()
    trace.set_annotator(lambda name, **stats: sentinel)
    try:
        assert trace.span("learner.run", n=1) is sentinel
    finally:
        trace.set_annotator(None)
    assert names(log) == ["learner.run"] and log.overflow == 3


@pytest.mark.parametrize("per_name, kept", [(10 ** 6, 500), (16, 16)])
def test_many_threads_fill_the_log_exactly_once(per_name, kept):
    """More threads than cores append and overflow together: each bound
    (the log's, a name's) holds, and nothing is lost between stored and
    counted."""
    log = StartupLog(bound=500, per_name=per_name)
    threads, each = 16, 200
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for i in range(each):
                if log.full or "ingest.admit" in log.closed:
                    log.dropped()
                else:
                    log.end(log.begin("ingest.admit", {"seq": i}))

        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    snap = log.snapshot()
    assert len(snap["entries"]) == kept
    assert len(snap["entries"]) + snap["overflow"] == threads * each
    assert all(e[4] == -1 and e[2] is not None for e in snap["entries"])


def test_a_loops_spans_leave_their_first_few_and_nothing_after(log):
    """What a training loop repeats is kept ``per_name`` times a name and
    then only counted, so the log does not fill under the loop (PR 52's
    first tree filled its last 3,000 entries during a window's first
    second, under one lock for forty threads); phases and the compile
    pipeline's events are start-up's own and have no such share."""
    assert log.per_name == startup_log.PER_NAME == 16
    for chunk in range(40):
        with trace.span("learner.chunk", chunk=chunk):
            with trace.span("learner.dispatch", chunk=chunk):
                pass
        with trace.span("ring.relayout", field="obs"):
            pass
        log.add("compile.trace", 0.0, fun_name="f")
    snap = log.snapshot()
    count = lambda name: sum(e[0] == name for e in snap["entries"])  # noqa: E731
    assert count("learner.chunk") == count("learner.dispatch") == 16
    assert count("ring.relayout") == count("compile.trace") == 40
    assert [e[5]["chunk"] for e in snap["entries"]
            if e[0] == "learner.dispatch"] == list(range(16))  # the first
    assert snap["overflow"] == 2 * 24 and snap["per_name"] == 16
    assert not log.full and log.closed == {"learner.chunk",
                                           "learner.dispatch"}
    # a name that has had its share is the bare span again
    assert trace.span("learner.chunk", chunk=41) is trace.NULL_SPAN
    with trace.span("learner.run", n=1) as sp:  # another name is kept
        sp.set_metadata(rows=0)
    assert names(log)[-1] == "learner.run"


def test_the_table_names_phases_compiles_and_what_is_unspanned():
    log = StartupLog()
    log.epoch -= 4.0  # of which the import below names 1.6 s
    index = log.begin("learner.first_dispatch", {"program": "learner.chunk"})
    log.add("cache.request")
    log.add("compile.backend", 0.25, fun_name="jit(fn)")
    log.add("compile.backend", 0.25, fun_name="jit(commit)")
    log.end(index)
    log._imported(log.epoch + 0.5, {"jax": 1.5, "d4pg_tpu": 0.1})
    lines = log.table().splitlines()
    assert all(line.startswith("[startup] ") for line in lines)
    text = "\n".join(lines)
    assert "x1   learner.first_dispatch" in text
    assert "1.500 s  x1   import.jax" in text
    assert "0.100 s  x1   import.d4pg_tpu" in text
    unspanned = float([line for line in lines if "unspanned" in line][0]
                      .split()[1])
    assert 2.3 < unspanned < 2.6
    assert "compile.backend 0.500 s" in text
    assert "1 program(s) asked the compile cache and missed: jit(fn)" in text
    assert "1 program(s) compiled outside the cache: jit(commit)" in text


def test_the_package_opens_the_log_before_anything_else_is_imported():
    """In a fresh process: the epoch is the package's first line, the first
    phase its own import, and a first import after it is timed."""
    import subprocess

    code = (
        "import time; t = time.monotonic(); import d4pg_tpu, builtins\n"
        "from d4pg_tpu.obs import startup_log as s\n"
        "assert t <= s.LOG.epoch <= time.monotonic()\n"
        "assert builtins.__import__ == s.LOG._timed_import\n"
        "import sys; assert 'jax' not in sys.modules\n"
        "import numpy\n"
        "e = s.LOG.snapshot()['entries']\n"
        "assert e[0][0] == 'import.d4pg_tpu' and e[0][1] == s.LOG.epoch\n"
        "assert e[0][6] and 'import.numpy' in [x[0] for x in e], e\n"
        "s.LOG.unwatch_imports()\n"
        "assert builtins.__import__ != s.LOG._timed_import\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_the_first_dispatch_of_each_program_is_a_phase_and_ends_the_watch(
        log, rng):
    """``FusedLoop`` over a ``ReplayService``: the chunk program's and the
    commit program's first calls are ``learner.first_dispatch`` phases with
    the compile beneath them, later calls are not, and the import hook is
    out after the chunk's."""
    import jax

    from d4pg_tpu.distributed.replay_service import ReplayService
    from d4pg_tpu.learner import D4PGConfig, init_state
    from d4pg_tpu.learner.loop import FusedLoop
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay
    from d4pg_tpu.replay.uniform import TransitionBatch

    obs, act, block, cap = 5, 2, 16, 64

    def rows(n):
        return TransitionBatch(
            obs=rng.standard_normal((n, obs)).astype(np.float32),
            action=rng.uniform(-1, 1, (n, act)).astype(np.float32),
            reward=rng.standard_normal(n).astype(np.float32),
            next_obs=rng.standard_normal((n, obs)).astype(np.float32),
            done=np.zeros(n, np.float32),
            discount=np.full(n, 0.99, np.float32))

    jax.monitoring.register_event_duration_secs_listener(startup._on_duration)
    jax.monitoring.register_scalar_listener(startup._on_scalar)
    log.watch_imports()
    try:
        config = D4PGConfig(obs_dim=obs, act_dim=act, v_min=-10, v_max=10,
                            n_atoms=11, hidden=(16, 16))
        buf = FusedDeviceReplay(cap, obs, act, alpha=0.6, block_rows=block,
                                staging_blocks=2)
        buf.add(rows(cap))
        buf.drain()
        service = ReplayService(buf)
        loop = FusedLoop(config, buf, k=2, batch_size=8, service=service)
        state = init_state(config, jax.random.key(0))
        try:
            assert builtins.__import__ == log._timed_import
            state, _m = loop.run(state, 2)
            assert builtins.__import__ != log._timed_import
            state, _m = loop.run(state, 4)
        finally:
            loop.close()
            service.close()
    finally:
        log.unwatch_imports()
        jax.monitoring.unregister_event_duration_listener(
            startup._on_duration)
        jax.monitoring.unregister_scalar_listener(startup._on_scalar)
    entries = log.snapshot()["entries"]
    firsts = [(i, e) for i, e in enumerate(entries)
              if e[0] == "learner.first_dispatch"]
    assert [e[5]["program"] for _i, e in firsts] == [
        "ingest.commit", "learner.chunk"]
    assert all(e[6] for _i, e in firsts)
    chunk_i, chunk = firsts[1]
    beneath = {e[0] for e in entries if e[4] == chunk_i}
    assert "learner.dispatch" in beneath
    traced = [e for e in entries if e[0] == "compile.backend"
              and chunk[1] <= e[1] and e[2] <= chunk[2]]
    assert traced and chunk[2] - chunk[1] >= sum(
        e[2] - e[1] for e in traced)
    phases = [e[0] for e in entries if e[6]]
    assert "learner.init_state" in phases and "replay.allocate" in phases
    # three dispatches, one first
    assert sum(e[0] == "learner.dispatch" for e in entries) == 3
