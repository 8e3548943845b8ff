"""Where set-up's seconds go, read from the program's own start-up log
(``d4pg_tpu/obs/startup_log.py``): what the seven per-layer readers under
``setup_s`` return, and the whole table on stderr.

The log is a bounded list of host intervals on ``time.monotonic()`` from an
epoch the program takes on the first line of its package: its **phases**
(first imports by top-level package and self time, ``startup.configure``,
``startup.backend``, ``learner.init_state``, ``replay.allocate``,
``ring.relayout``, ``learner.first_dispatch`` of each registered program) lie
on the main thread, do not overlap, and so add; beneath them lie the compile
pipeline's events (``compile.trace``, ``compile.lower``, ``compile.backend``,
``cache.request``, ``cache.hit``, ``cache.load``) and every other program
span. Set-up ends where the window's first ``learner.run`` starts. The
window is known on the profiler's clock (``bench.window``); the spans that
are in the log AND in the trace (``learner.dispatch`` and ``learner.chunk``,
paired by their ``chunk`` number) give the offset between the two clocks:
its median is used, its spread printed.

| metric | what it is |
| --- | --- |
| ``import_s`` | the ``import.*`` phases before the window, summed |
| ``backend_init_s`` | ``startup.backend`` |
| ``first_dispatch_s`` | the ``learner.first_dispatch`` phases |
| ``trace_lower_s`` | the union of the ``compile.trace`` and ``compile.lower`` intervals before the window (they nest, and lie inside phases: not additive) |
| ``cache_load_s`` | ``cache.load`` before the window (likewise) |
| ``cache_miss_programs`` | ``compile.backend`` entries whose thread logged a ``cache.request`` and no ``cache.hit`` for them; names on stderr, beside the programs compiled with the cache off |
| ``setup_unspanned_s`` | the epoch to the end of set-up, less the union of the phases: what the program does not name (the harness's own work is in it) |

**Over a program that keeps no such log** (the parent of PR 52, over which the
driver lays these files) ``program_log`` returns ``None`` and every reader
returns the float 0.0 at once, having read nothing of ``ctx`` but whether it
holds a trace, and says so once on stderr. It cannot leave the metric out:
``manifest.validate_line`` refuses a traced line that lacks a listed metric
(the convention of ``program_trace`` and ``row_journey``). Nothing here reads
or writes a key of ``ctx`` that a driver sets, apart from ``trace``: the
analysis is kept under this module's own key.
"""

from __future__ import annotations

import os
import statistics
import sys
import time

METRICS = ("import_s", "backend_init_s", "first_dispatch_s", "trace_lower_s",
           "cache_load_s", "cache_miss_programs", "setup_unspanned_s")
KEY = "startup_phases"  # this module's own key in ``ctx``
PAIRED = ("learner.dispatch", "learner.chunk")  # in the log and the trace
RUN = "learner.run"
SLACK_S = 2e-3  # a window's first run against the window's start, mapped


def say(msg: str) -> None:
    print("[startup_phases] " + msg, file=sys.stderr, flush=True)


def program_log() -> dict | None:
    """The program's start-up log as ``{"epoch", "bound", "overflow",
    "entries": [(name, t0, t1, thread, parent, stats, phase), ...]}``, or
    ``None`` for a program that keeps none: no such module, no such names,
    or a log with no entries. THE one place that knows a program may lack
    it."""
    try:
        from d4pg_tpu.obs import startup_log

        snap = startup_log.LOG.snapshot()
        return snap if snap["entries"] else None
    except (ImportError, AttributeError, KeyError, TypeError):
        return None


def union_s(intervals) -> float:
    """Length of the union of ``(t0, t1)`` intervals."""
    total, reach = 0.0, None
    for t0, t1 in sorted(intervals):
        if reach is None or t0 > reach:
            total += t1 - t0
            reach = t1
        elif t1 > reach:
            total += t1 - reach
            reach = t1
    return total


def clock_offset(entries: list, spans: list) -> list:
    """``log clock - trace clock`` of every span that is in both: the
    ``PAIRED`` names, matched by their ``chunk`` stat."""
    in_trace = {}
    for name, start, _end, stats in spans:
        if name in PAIRED and "chunk" in stats:
            in_trace[name, int(stats["chunk"])] = start
    return [t0 - in_trace[name, int(stats["chunk"])]
            for name, t0, _t1, _th, _p, stats, _ph in entries
            if name in PAIRED and "chunk" in stats
            and (name, int(stats["chunk"])) in in_trace]


def end_of_setup(snap: dict, spans: list, window: tuple) -> tuple:
    """``(end on the log's clock, offsets, how it was found)``: the start of
    the first ``learner.run`` the log kept inside ``bench.window``; where
    the log was full before the window (or no span is in both), the end of
    the last entry it has."""
    entries = snap["entries"]
    offsets = clock_offset(entries, spans)
    if offsets:
        opened = window[0] + statistics.median(offsets)
        for name, t0, _t1, _th, parent, _st, _ph in entries:
            if name == RUN and parent < 0 and t0 >= opened - SLACK_S:
                return t0, offsets, "the window's first learner.run"
    last = max((e[2] for e in entries if e[2] is not None),
               default=snap["epoch"])
    return last, offsets, "the last entry the log has (no span of the " \
        "window is in the log: set-up's end is read short)"


def compiled(entries: list) -> list:
    """``(fun_name, "hit" | "miss" | "uncached")`` of each
    ``compile.backend`` entry, by the cache events its thread logged since
    its previous one."""
    seen: dict = {}
    out = []
    for name, _t0, _t1, thread, _p, stats, _ph in entries:
        if name in ("cache.request", "cache.hit"):
            seen.setdefault(thread, set()).add(name)
        elif name == "compile.backend":
            events = seen.pop(thread, set())
            out.append((str(stats.get("fun_name", "?")),
                        "hit" if "cache.hit" in events else
                        "miss" if "cache.request" in events else "uncached"))
    return out


def _counted(names: list) -> str:
    counts: dict = {}
    for n in names:
        counts[n] = counts.get(n, 0) + 1
    return ", ".join(n if c == 1 else f"{n} x{c}"
                     for n, c in counts.items()) or "-"


def reduce(snap: dict, spans: list, window: tuple) -> dict:
    """The seven numbers (and ``setup_s``, ``phases``, ``first_dispatch``
    for whoever prints or checks them) of one log against one trace."""
    end, offsets, how = end_of_setup(snap, spans, window)
    done = [e for e in snap["entries"] if e[2] is not None and e[2] <= end]
    phases: dict = {}  # name -> [seconds, count], in order of appearance
    for name, t0, t1, _th, _p, _st, phase in done:
        if phase:
            took = phases.setdefault(name, [0.0, 0])
            took[0] += t1 - t0
            took[1] += 1
    named = lambda prefix: sum(  # noqa: E731
        s for n, (s, _c) in phases.items() if n.startswith(prefix))
    of = lambda *names: [(e[1], e[2]) for e in done  # noqa: E731
                         if e[0] in names]
    setup_s = end - snap["epoch"]
    kinds = compiled(done)
    out = {
        "import_s": named("import."),
        "backend_init_s": named("startup.backend"),
        "first_dispatch_s": named("learner.first_dispatch"),
        "trace_lower_s": union_s(of("compile.trace", "compile.lower")),
        "cache_load_s": sum(t1 - t0 for t0, t1 in of("cache.load")),
        "cache_miss_programs": float(sum(k == "miss" for _n, k in kinds)),
        "setup_unspanned_s": setup_s - union_s(
            (e[1], e[2]) for e in done if e[6]),
        "setup_s": setup_s, "phases": phases, "offsets": offsets,
        "end_found_by": how, "compiled": kinds, "first_dispatch": [],
        "rest": {},
    }
    # each first dispatch: what of it the compile pipeline names
    for name, t0, t1, thread, _p, stats, phase in done:
        if name == "learner.first_dispatch" and phase:
            inside = lambda n: [  # noqa: E731
                (e[1], e[2]) for e in done if e[0] == n and e[3] == thread
                and t0 <= e[1] and e[2] <= t1]
            trace, lower, backend = (union_s(inside(n)) for n in (
                "compile.trace", "compile.lower", "compile.backend"))
            covered = union_s(inside("compile.trace") + inside(
                "compile.lower") + inside("compile.backend"))
            out["first_dispatch"].append(
                (str(stats.get("program", "?")), t1 - t0, trace, lower,
                 backend, t1 - t0 - covered))
        if name == "import.d4pg_tpu" and phase:  # the remainder's parts
            for package, s in stats.items():
                out["rest"][package] = out["rest"].get(package, 0.0) \
                    + float(s)
    return out


def report(snap: dict, got: dict) -> None:
    offsets = got["offsets"]
    say(f"set-up by the program's log: {got['setup_s']:.3f} s from the "
        f"log's epoch to {got['end_found_by']}; "
        f"{len(snap['entries'])} entries, {snap['overflow']} counted past "
        f"the bounds ({snap['bound']} entries, {snap.get('per_name', '-')} "
        f"a span's name)")
    if offsets:
        say(f"log clock - trace clock: median "
            f"{statistics.median(offsets):.6f} s, spread "
            f"{(max(offsets) - min(offsets)) * 1e6:.1f} us over "
            f"{len(offsets)} spans that are in both")
    say("phases (they add): " + ", ".join(
        f"{n} {s:.3f} s" + (f" x{c}" if c > 1 else "")
        for n, (s, c) in got["phases"].items()))
    say(", ".join(f"{m} {got[m]:.3f}" for m in METRICS)
        + "; the phases + setup_unspanned_s = "
        f"{sum(s for s, _c in got['phases'].values()) + got['setup_unspanned_s']:.3f} s")
    if got["rest"]:
        top = sorted(got["rest"].items(), key=lambda kv: -kv[1])[:8]
        say("import.d4pg_tpu, the remainder, is mostly: " + ", ".join(
            f"{p} {s:.3f} s" for p, s in top))
    for program, took, trace, lower, backend, own in got["first_dispatch"]:
        say(f"learner.first_dispatch of {program}: {took:.3f} s = trace "
            f"{trace:.3f} + lower {lower:.3f} + backend (a compile, or the "
            f"key and the load) {backend:.3f} + self {own:.3f}")
    for kind, what in (("miss", "asked the compile cache and missed"),
                       ("uncached", "compiled with the cache off")):
        names = [n for n, k in got["compiled"] if k == kind]
        say(f"{len(names)} program(s) {what} before the window: "
            f"{_counted(names)}")


def analyse(ctx: dict) -> dict:
    """The seven numbers, computed once a run and kept under ``KEY``.
    ``ctx["startup_log"]`` / ``ctx["startup_spans"]`` (the tests' fixture)
    stand in for the program's log and the run's trace file."""
    if KEY in ctx:
        return ctx[KEY]
    t = time.perf_counter()
    snap = ctx["startup_log"] if "startup_log" in ctx else program_log()
    if snap is None:
        say("the program keeps no start-up log (a program older than PR "
            f"52): {', '.join(METRICS)} read 0.0")
        ctx[KEY] = dict.fromkeys(METRICS, 0.0)
        return ctx[KEY]
    if "startup_spans" in ctx:
        spans = ctx["startup_spans"]
    else:
        from benchmark import manifest, program_trace, trace_reduce

        spans = program_trace.host_spans(trace_reduce.newest_xplane(
            os.path.join(manifest.REPO, ".bench_trace")))
    got = reduce(snap, spans, ctx["trace"].window)
    report(snap, got)
    say(f"read in {time.perf_counter() - t:.2f} s")
    ctx[KEY] = got
    return got


def read(ctx: dict, metric: str):
    """A reader's whole body: nothing without a trace, else the number."""
    if ctx.get("trace") is None:
        return None
    return float(analyse(ctx)[metric])
