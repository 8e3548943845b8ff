"""The start-up log: where a process's seconds go before its first chunk.

A bounded list of host intervals on ``time.monotonic()``, opened on the
first line of ``d4pg_tpu/__init__.py`` (the ``epoch``: as near process
start as the program can take it) and fed from three places:

- ``obs.trace.span()``: every program span is kept here as well as handed
  to the profiler, whether or not a profiler is attached;
- ONE hook on ``builtins.__import__`` that times first imports by SELF time
  (an import's seconds less those of the imports nested in it), by
  top-level package: what ``orbax.checkpoint`` imports of ``google`` or
  ``jax`` for the first time is booked under ``import.google`` /
  ``import.jax``, not under ``import.orbax``. The hook goes in when the
  log opens and comes out at the end of the chunk program's first dispatch
  (``unwatch_imports``) or when the log is full: nothing of it is left
  under a training loop;
- ``jax.monitoring``'s compile-pipeline events, which
  ``startup.configure`` forwards (``add``): this module imports no jax.

An entry is ``[name, t0, t1, thread, parent, stats, phase]``: ``parent``
the index of the entry open on the same thread when this one began (-1:
none), ``t1`` ``None`` while open. **Phases** are the entries that add up:
on the main thread, named in ``PHASES`` or ``import.<package>``, and not
inside another phase. An import made inside a phase (a lazy import under
``startup.backend``) is that phase's, kept as a plain entry beneath it.

Bounded twice: at most ``bound`` entries are stored, and of a span's name
that is no phase at most ``per_name``; everything past either is counted
(``overflow``) and never stored, and ``full`` or the name's place in
``closed`` turns ``span()`` into a compare and a lookup (``obs/trace.py``).
The second bound is what keeps the log out of a training loop: a loop's
spans (seven a chunk on the learner's thread, two an add on the adding
threads and the commit thread: 3,000 a second in the ingest cell) would
else fill what a start-up leaves free under the loop's own steady state
(this PR's first tree: 3,940 entries in the first 1.2 s of that cell's
window, under a lock 34 threads share, and a dozen more collections in the
window than without the log; PERF.md section 6, PR 52). With it a loop
leaves ``per_name`` entries a name in its first chunks, enough for a trace
reader to pair the two clocks, and nothing after. No switch: always
on, always bounded.

Names (PERF.md section 3 says which metric reads which):
``import.<package>``, ``startup.configure``, ``startup.backend``,
``learner.init_state``, ``replay.allocate``, ``ring.relayout``,
``learner.first_dispatch`` (``program=``); ``compile.trace``,
``compile.lower``, ``compile.backend`` (``fun_name=``); ``cache.request``,
``cache.hit``, ``cache.load``; and every other program span
(``learner.run`` ...), which a trace reader pairs with the profiler's
copy to put the two clocks on one axis.
"""

from __future__ import annotations

import builtins
import sys
import threading
import time

# room for a cold start of ``train`` (its eager ``init_state`` alone compiles
# some seventy small programs, four to six entries each: 388 entries to the
# end of the first cycle on the CPU, a benchmark cell 350-900 to its window)
# several times over: a program with more to compile has more to log
BOUND = 4096
# of one span's name (phases apart: they are start-up's own, a few each):
# the first chunks of a loop, of which a benchmark cell makes three before
# its window, so that a traced window leaves a dozen pairs of
# ``learner.chunk`` / ``learner.dispatch`` for the clocks and its first
# ``learner.run``
PER_NAME = 16
PHASES = frozenset({
    "startup.configure", "startup.backend", "learner.init_state",
    "replay.allocate", "ring.relayout", "learner.first_dispatch"})
IMPORT = "import."
# top-level packages with a phase of their own: 0.3 s or more of self time
# in ``python -X importtime`` of cells 1 and 7 on the chip machine (google
# 9.8 s, jax 1.7, orbax 0.65, numpy 0.45, jaxlib 0.35; PERF.md section 5),
# and flax and optax (0.2 each). Any other package that reaches
# ``NAME_AT_S`` in one import statement is named too; the rest (the
# program's own modules, the standard library, small packages) is the one
# remainder ``import.d4pg_tpu``.
NAMED = frozenset({"jax", "jaxlib", "numpy", "orbax", "google", "flax",
                   "optax"})
REMAINDER = "d4pg_tpu"
NAME_AT_S = 0.3
# an import statement shorter than this leaves no entry (one that finds its
# module in ``sys.modules`` takes about a microsecond)
MIN_IMPORT_S = 1e-3
REST_NAMES_AT_S = 0.05  # a package of the remainder that its stats name


class StartupLog:
    """The bounded log. One per process (``LOG``); tests make their own.
    The lock is ``_mu``, terminal like every lock of this package."""

    def __init__(self, bound: int = BOUND, epoch: float | None = None,
                 per_name: int = PER_NAME):
        self.epoch = time.monotonic() if epoch is None else float(epoch)
        self.bound = int(bound)
        self.per_name = int(per_name)
        self.full = self.bound <= 0
        self.closed: set = set()  # span names that have had ``per_name``
        self._kept: dict = {}  # span name -> entries kept, under ``_mu``
        self._mu = threading.Lock()
        self._entries: list[list] = []
        self._drops: list[list] = []  # one counter a thread, see dropped()
        self._local = threading.local()  # .open: indices; .imports, .self_s
        self._main = threading.main_thread().ident
        self._phase = -1  # index of the phase open on the main thread
        self._import = None  # the import function the hook stands before
        self._watching = False

    # -- entries ------------------------------------------------------------
    def _stack(self) -> list:
        try:
            return self._local.open
        except AttributeError:
            self._local.open = []
            return self._local.open

    def _store(self, name: str, t0: float, t1, stats: dict,
               may_be_phase: bool, repeats: bool = False) -> int:
        """Append one entry; -1 (counted) once the bound is reached or,
        for a span that ``repeats``, once its name has had its share."""
        ident = threading.get_ident()
        stack = self._stack()
        with self._mu:
            full = len(self._entries) >= self.bound
            if full:
                self.full = True
            elif not repeats or self._kept.get(name, 0) < self.per_name:
                if repeats:
                    kept = self._kept[name] = self._kept.get(name, 0) + 1
                    if kept >= self.per_name:
                        self.closed.add(name)
                index = len(self._entries)
                phase = (may_be_phase and ident == self._main
                         and self._phase < 0)
                self._entries.append([name, t0, t1, ident,
                                      stack[-1] if stack else -1, stats,
                                      phase])
                if phase and t1 is None:
                    self._phase = index
                return index
        self.dropped()
        if full:
            self.unwatch_imports()
        return -1

    def begin(self, name: str, stats: dict) -> int:
        """Open an entry on this thread; its index, or -1 when it was only
        counted."""
        phase = name in PHASES
        index = self._store(name, time.monotonic(), None, stats, phase,
                            not phase)
        if index >= 0:
            self._local.open.append(index)
        return index

    def end(self, index: int) -> None:
        if index < 0:
            return
        self._entries[index][2] = time.monotonic()
        stack = self._stack()
        if stack and stack[-1] == index:
            stack.pop()
        elif index in stack:  # closed out of order: drop it and its children
            del stack[stack.index(index):]
        if self._phase == index:
            self._phase = -1

    def annotate(self, index: int, stats: dict) -> None:
        """Stats known only inside the span."""
        if index >= 0:
            self._entries[index][5].update(stats)

    def add(self, name: str, seconds: float = 0.0, **stats) -> None:
        """A closed entry that ended now and took ``seconds`` (an event
        reported when it is over; 0: an instant)."""
        if self.full:
            self.dropped()
            return
        t1 = time.monotonic()
        self._store(name, t1 - seconds, t1, stats, False)

    def dropped(self) -> None:
        """Count an entry that was not stored (``span()`` once ``full``):
        exact and without the lock, each thread counting in a cell of its
        own."""
        try:
            cell = self._local.dropped
        except AttributeError:
            cell = self._local.dropped = [0]
            with self._mu:
                self._drops.append(cell)
        cell[0] += 1

    @property
    def overflow(self) -> int:
        """Entries past the bound: counted, never stored."""
        with self._mu:
            return sum(cell[0] for cell in self._drops)

    def snapshot(self) -> dict:
        """``{"epoch", "bound", "per_name", "overflow", "entries"}``, the
        entries as tuples ``(name, t0, t1, thread, parent, stats, phase)``
        in the order they began."""
        with self._mu:
            entries = [(e[0], e[1], e[2], e[3], e[4], dict(e[5]), e[6])
                       for e in self._entries]
        return {"epoch": self.epoch, "bound": self.bound,
                "per_name": self.per_name, "overflow": self.overflow,
                "entries": entries}

    # -- first imports ------------------------------------------------------
    def watch_imports(self) -> None:
        """Stand before ``builtins.__import__`` (once)."""
        if self._import is None and not self.full:
            self._import = builtins.__import__
            self._watching = True
            builtins.__import__ = self._timed_import

    def unwatch_imports(self) -> None:
        """Take the hook out; where another hook was put in after this one,
        it stays in place behind it as a bare pass-through."""
        if self._import is not None \
                and builtins.__import__ == self._timed_import:
            builtins.__import__ = self._import
        self._watching = False

    def _timed_import(self, name, globals=None, locals=None, fromlist=(),
                      level=0):
        if not self._watching or (level == 0 and not fromlist
                                  and name in sys.modules):
            return self._import(name, globals, locals, fromlist, level)
        local = self._local
        try:
            frames = local.imports
        except AttributeError:
            frames, local.self_s = [], {}
            local.imports = frames
        nested = [0.0]  # seconds of the imports nested in this one
        frames.append(nested)
        t0 = time.monotonic()
        try:
            return self._import(name, globals, locals, fromlist, level)
        finally:
            t1 = time.monotonic()
            frames.pop()
            if level:
                name = (globals or {}).get("__package__") or \
                    (globals or {}).get("__name__") or ""
            package = name.partition(".")[0]
            self_s = local.self_s
            self_s[package] = self_s.get(package, 0.0) + (t1 - t0) - nested[0]
            if frames:
                frames[-1][0] += t1 - t0
            else:
                if t1 - t0 >= MIN_IMPORT_S:
                    self._imported(t0, dict(self_s))
                self_s.clear()

    def _imported(self, t0: float, self_s: dict) -> None:
        """One outermost import statement, as consecutive entries from
        ``t0``: one a named package with its self time, then the
        remainder (its stats: ``{package: seconds}`` of its largest parts). Their lengths are true and add up to the statement's;
        where each lies inside the statement is not (the packages'
        modules interleave)."""
        named = {p: s for p, s in self_s.items()
                 if p != REMAINDER and (p in NAMED or s >= NAME_AT_S)
                 and s >= MIN_IMPORT_S}
        for package, seconds in named.items():
            self._store(IMPORT + package, t0, t0 + seconds, {}, True)
            t0 += seconds
        rest = {p: s for p, s in self_s.items() if p not in named}
        if rest:
            # the remainder says which packages are the most of it
            self._store(IMPORT + REMAINDER, t0, t0 + sum(rest.values()),
                        {p: round(s, 4) for p, s in rest.items()
                         if s >= REST_NAMES_AT_S}, True)

    # -- the operator's table -----------------------------------------------
    def table(self) -> str:
        """The ``[startup]`` table ``train`` prints when its first chunks
        are done: each phase (seconds, count) and what no phase names, from
        the epoch to now; the compile pipeline's totals (they lie inside
        the phases and add to nothing); the programs compiled with no
        cache hit."""
        end = time.monotonic()
        snap = self.snapshot()
        entries = [e for e in snap["entries"] if e[2] is not None]
        phases: dict = {}
        for name, t0, t1, *_rest, phase in entries:
            if phase:
                took, n = phases.get(name, (0.0, 0))
                phases[name] = (took + t1 - t0, n + 1)
        total = end - snap["epoch"]
        lines = [f"[startup] {total:8.3f} s since the package was first "
                 f"imported; {len(snap['entries'])} entries kept, "
                 f"{snap['overflow']} counted past the bounds "
                 f"({snap['bound']} entries, {snap['per_name']} a span's "
                 f"name)"]
        lines += [f"[startup] {took:8.3f} s  x{n:<3d} {name}"
                  for name, (took, n) in phases.items()]
        spanned = sum(took for took, _n in phases.values())
        lines.append(f"[startup] {total - spanned:8.3f} s       unspanned")
        sums = {name: sum(e[2] - e[1] for e in entries if e[0] == name)
                for name in ("compile.trace", "compile.lower",
                             "compile.backend", "cache.load")}
        lines.append("[startup] inside the phases: " + ", ".join(
            f"{name} {took:.3f} s" for name, took in sums.items()))
        kinds = compiled(entries)
        for kind, what in (("miss", "asked the compile cache and missed"),
                           ("uncached", "compiled outside the cache")):
            names = [name for name, k in kinds if k == kind]
            counts = {n: names.count(n) for n in dict.fromkeys(names)}
            lines.append(f"[startup] {len(names)} program(s) {what}: "
                         + (", ".join(n if c == 1 else f"{n} x{c}"
                                      for n, c in counts.items()) or "-"))
        return "\n".join(lines)


def compiled(entries: list) -> list:
    """``(fun_name, "hit" | "miss" | "uncached")`` of every
    ``compile.backend`` entry, by the cache events its thread logged since
    its previous one: a request and a hit, a request alone, or none (a
    program compiled with the cache off, ``io/profiling.fresh_compile``)."""
    seen: dict = {}  # thread -> cache events since its last compile
    out = []
    for name, _t0, _t1, thread, _parent, stats, _phase in entries:
        if name in ("cache.request", "cache.hit"):
            seen.setdefault(thread, set()).add(name)
        elif name == "compile.backend":
            events = seen.pop(thread, set())
            out.append((stats.get("fun_name", "?"),
                        "hit" if "cache.hit" in events else
                        "miss" if "cache.request" in events else "uncached"))
    return out


# THE process-wide log. ``d4pg_tpu/__init__.py`` opens it (the epoch, the
# import hook); a process that imports only this module gets the log with
# this import as its epoch and no hook.
LOG = StartupLog()


def open_log(epoch: float) -> None:
    """What the first line of the package does: the epoch as taken there,
    the time since then as the first ``import.d4pg_tpu`` phase (this
    package's own import), and the import hook."""
    LOG.epoch = float(epoch)
    now = time.monotonic()
    LOG._store(IMPORT + REMAINDER, LOG.epoch, now, {}, True)
    LOG.watch_imports()
