"""Wire-to-grad trace spans: where does a frame's time go?

The ROADMAP's perf items (multi-core K-sweep attribution, IMPACT-style
multi-learner, sample-on-ingest) all need one measurement the repo
could not make: the latency decomposition between an actor's socket
write and the grad step that consumes the rows. This module is that
measurement plane.

Mechanics: the SENDER samples frames at ``trace_sample`` (seeded rng —
fleet runs stay reproducible) and stamps the sampled frame's v2 wire
header with a trace id + birth timestamp (``transport.encode_raw``
extension; frames without the extension decode unchanged forever, npz
frames are never traced). The receiver records a span timestamp at
each stage the frame passes:

    send ──> admission ──> decode ──> stage ──> merge ──> commit
                 │             │                  │            │
                 └── shed ─────┴──── shed ────────┘            │
                                                               v
                        done <── grad <── land <── h2d <───────┘
                                                   (fused path only)

- ``admission``  — the frame entered an ingest shard's deque
  (``ReplayService.add_payload``; zero-decode for v2 frames).
- ``decode``     — the shard worker parsed the columns.
- ``stage``      — rows staged (direct-stage ring copy, or handed to
  the ordered-merge inbox on the non-fused path).
- ``merge``      — the commit thread popped the ticket in global order.
- ``commit``     — rows landed in replay state (buffer insert /
  direct-stage accounting settled). On the FUSED path "replay state" is
  the HOST staging ring (``FusedDeviceReplay.add``), and the service
  keeps with the trace the POSITION of the group's last row in the
  stream of rows pushed into host staging (``mark_committed(tids,
  through=...)``): the same position the program's spans say
  (``ingest.host_stage`` ``through``, ``fused.stage_block`` ``first`` /
  ``through``, ``learner.dispatch`` ``landed``), so a sampled stamp and
  a traced run follow one definition of the row's journey.
- ``h2d``        — fused path: the ``fused.stage_block`` whose block
  carries that position started its ``device_put``.
- ``land``       — fused path: that block's ``fused.commit_staged``
  dispatched the ring write + tree insert.
- ``grad``       — the DISPATCH of the first learner consumption that can
  see the rows. On the fused path ``FusedLoop`` calls
  ``mark_grad(landed=...)`` after each chunk dispatch and only traces
  whose position has landed are stamped: the first chunk that can sample
  the rows. The host-sampled loops, the dealt loop and the fleet
  harnesses call the bare ``mark_grad()`` after a ``sample()`` /
  dispatch, which stamps everything committed (their rows are in replay
  state at ``commit``).
- ``done``       — fused path: the END on the device of the chunk that
  stamped ``grad``, taken by one daemon thread that blocks on a small
  output of that chunk. ``wire_to_done`` is the headline there;
  ``wire_to_grad`` is send -> the dispatch and is short of it by the
  wait behind the chunk already queued plus the chunk itself.

A traced frame whose rows the host staging ring dropped before any block
carried them gets a terminal ``shed`` (``shed_dropped``).

A shed/tombstoned/undecodable frame gets a terminal ``shed`` span so
every admitted trace terminates — the zero-orphan invariant the K-shard
propagation test pins.

Clock: ``time.monotonic()`` throughout. On Linux that is
CLOCK_MONOTONIC, one timeline across processes on a host, so spawned
actor lanes stamp births the receiver's spans compare against directly.

Cost: a span is one terminal-lock round trip + one dict store (~1 us);
at the default 2% sample over 16-row frames that is ~1.3 ns/row —
unmeasurable against the ~190 us/row ingest budget. The recorder is
disabled by default; ``enable()`` is the only switch.

Program spans and the program table (below the recorder) are a separate
mechanism: ``span()`` hands the learner's and the ingest plane's own
boundaries to the profiler (the span store of a traced window) and keeps
them in the start-up log (``obs/startup_log.py``) until their name has had
its share there or the log is full, and
the table lets a trace reader turn a ``jax.named_scope`` inside a compiled
program into device time.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque

from d4pg_tpu.obs.containment import contained_crash
from d4pg_tpu.obs.registry import percentile_summary
from d4pg_tpu.obs.startup_log import LOG as _LOG

# The default sampling rate the --trace_sample knobs document: dense
# enough for stable p99s over a 10 s fleet run, sparse enough that the
# acceptance overhead bound (<= 2%) holds with an order of magnitude of
# margin.
DEFAULT_SAMPLE = 0.02

# Pipeline stages in order; `shed` is the failure terminal. ``deal`` is
# the sample-on-ingest plane's post-commit stage: the dealer stamps the
# NEWEST constituent frame of each dealt block, so a block's deal span is
# a child of a committed trace. Terminals are unchanged — commit already
# terminates a trace, so a dealt block lost to a learner kill can never
# orphan the accounting.
STAGES = ("send", "admission", "decode", "stage", "merge", "commit", "deal",
          "h2d", "land", "grad", "done")
TERMINALS = ("commit", "grad", "shed")

# Stage pairs the latency block reports (label, from, to).
_PAIRS = (
    ("wire_to_admission", "send", "admission"),
    ("admission_to_decode", "admission", "decode"),
    ("decode_to_stage", "decode", "stage"),
    ("stage_to_merge", "stage", "merge"),
    ("merge_to_commit", "merge", "commit"),
    ("commit_to_deal", "commit", "deal"),
    ("deal_to_grad", "deal", "grad"),
    ("commit_to_grad", "commit", "grad"),
    ("commit_to_h2d", "commit", "h2d"),
    ("h2d_to_land", "h2d", "land"),
    ("land_to_grad", "land", "grad"),
    ("grad_to_done", "grad", "done"),
    ("wire_to_commit", "send", "commit"),
    ("wire_to_grad", "send", "grad"),
    ("wire_to_done", "send", "done"),
)

_tid_counter = itertools.count(1)  # next() is GIL-atomic in CPython


def new_trace_id(salt: int = 0) -> int:
    """Process-unique u64 trace id; ``salt`` (e.g. an actor index)
    decorrelates ids across sender processes sharing a receiver."""
    return ((salt & 0xFFFF) << 48) | (next(_tid_counter) & 0xFFFFFFFFFFFF)


class TraceRecorder:
    """Receiver-side span table, keyed by trace id.

    Bounded: at most ``max_traces`` live records; past the bound new
    traces are dropped and counted (``overflow``) — the plane degrades
    by losing samples, never by growing without bound. All mutation
    under one terminal lock (``_mu``; see obs/__init__ discipline)."""

    def __init__(self, max_traces: int = 8192):
        self._mu = threading.Lock()
        self.max_traces = int(max_traces)
        self._spans: OrderedDict[int, dict] = OrderedDict()
        # (tid, position of the trace's last row in host staging or None),
        # in commit order: positions ascend
        self._await_grad: deque = deque()
        # stage -> (highest position stamped, when): a trace committed just
        # after its block moved on takes the block's own stamp
        self._reached: dict[str, tuple[int, float]] = {}
        # (output of a dispatched chunk, tids it stamped ``grad``) and the
        # thread that blocks on them, alive only while there is one
        self._watching: deque = deque()
        self._watcher: threading.Thread | None = None
        self.enabled = False
        self.sample_rate = 0.0
        self.overflow = 0

    # -- lifecycle ----------------------------------------------------------
    def enable(self, sample_rate: float = DEFAULT_SAMPLE) -> None:
        with self._mu:
            self.enabled = True
            self.sample_rate = float(sample_rate)

    def disable(self) -> None:
        with self._mu:
            self.enabled = False

    def reset(self) -> None:
        with self._mu:
            self._spans.clear()
            self._await_grad.clear()
            self._reached.clear()
            self._watching.clear()
            self.overflow = 0

    # -- span recording (hot path) ------------------------------------------
    def begin(self, tid: int, birth_ts: float) -> None:
        """Open a trace at admission with the sender's birth stamp."""
        if not self.enabled:
            return
        with self._mu:
            if tid in self._spans:
                return
            if len(self._spans) >= self.max_traces:
                # evict the oldest COMPLETED record; if none, drop the
                # new trace (live records must keep accumulating spans)
                evicted = False
                for old_tid, spans in self._spans.items():
                    if any(t in spans for t in TERMINALS):
                        del self._spans[old_tid]
                        evicted = True
                        break
                if not evicted:
                    self.overflow += 1
                    return
            self._spans[tid] = {"send": float(birth_ts)}

    def record_span(self, tid: int, stage: str, ts: float | None = None
                    ) -> None:
        if not self.enabled:
            return
        t = time.monotonic() if ts is None else ts
        with self._mu:
            spans = self._spans.get(tid)
            if spans is not None and stage not in spans:
                spans[stage] = t

    def terminal_shed(self, tid: int) -> None:
        """Terminal span for a frame that left the pipeline early (shed,
        tombstoned, undecodable). Opens the record if admission never
        stamped it (admission-reject path)."""
        if not self.enabled:
            return
        t = time.monotonic()
        with self._mu:
            spans = self._spans.get(tid)
            if spans is None:
                if len(self._spans) >= self.max_traces:
                    self.overflow += 1
                    return
                spans = self._spans[tid] = {}
            spans.setdefault("shed", t)

    def mark_committed(self, tids, through: int | None = None) -> None:
        """Commit spans for a merged group + queue them for the next
        grad-consumption mark. ``through``: the position of the group's
        last row in the buffer's host staging stream (``None`` for a
        buffer without one: the rows are in replay state already)."""
        if not self.enabled:
            return
        t = time.monotonic()
        with self._mu:
            for tid in tids:
                spans = self._spans.get(tid)
                if spans is not None and "commit" not in spans:
                    spans["commit"] = t
                    self._await_grad.append((tid, through))
                    if through is not None:
                        for stage, (pos, at) in self._reached.items():
                            if through <= pos:
                                spans[stage] = at

    def mark_through(self, stage: str, through: int) -> None:
        """Stamp ``stage`` (``h2d``, ``land``) on every commit-pending
        trace whose position is at most ``through``: the block that
        carries its rows has reached that stage. Near-free when nothing
        is pending."""
        if not self._await_grad:
            return
        t = time.monotonic()
        with self._mu:
            self._reached[stage] = (through, t)
            for tid, pos in self._await_grad:
                if pos is None:
                    continue
                if pos > through:
                    break
                spans = self._spans.get(tid)
                if spans is not None and stage not in spans:
                    spans[stage] = t

    def shed_dropped(self, through: int) -> int:
        """Terminal ``shed`` for every commit-pending trace at a position
        up to ``through`` that no block carried (no ``h2d``): the host
        staging ring dropped its rows to admit newer ones."""
        if not self._await_grad:
            return 0
        t = time.monotonic()
        n = 0
        with self._mu:
            kept = deque()
            for tid, pos in self._await_grad:
                spans = self._spans.get(tid)
                if (pos is not None and pos <= through and spans is not None
                        and "h2d" not in spans):
                    spans.setdefault("shed", t)
                    n += 1
                else:
                    kept.append((tid, pos))
            self._await_grad = kept
        return n

    def mark_grad(self, ts: float | None = None, landed: int | None = None,
                  done=None) -> int:
        """Stamp commit-pending traces with grad-consumption time. Bare
        (the host-sampled loops, the dealt loop, the fleet harness's
        consumer lane after a ``sample()``): every pending trace.
        ``landed=`` (the fused loop, right after a chunk dispatch): only
        traces whose position has landed on the device, i.e. the first
        chunk that can sample their rows. ``done``: a small output of that
        chunk; the watcher thread blocks on it and stamps ``done``.
        Near-free when nothing is pending (one unlocked emptiness probe,
        benign race under the GIL)."""
        if not self._await_grad:
            return 0
        t = time.monotonic() if ts is None else ts
        stamped = []
        with self._mu:
            while self._await_grad:
                tid, pos = self._await_grad[0]
                if landed is not None and pos is not None and pos > landed:
                    break
                self._await_grad.popleft()
                spans = self._spans.get(tid)
                if spans is not None and "grad" not in spans:
                    spans["grad"] = t
                    stamped.append(tid)
            if stamped and done is not None and self.enabled:
                self._watching.append((done, stamped))
                if self._watcher is None:
                    self._watcher = threading.Thread(
                        target=self._watch, daemon=True, name="trace-done")
                    self._watcher.start()
        return len(stamped)

    def _watch(self) -> None:
        """Block on each chunk's output in turn and stamp ``done`` at its
        end; leaves when nothing is left to wait for."""
        try:
            while True:
                with self._mu:
                    if not self._watching:
                        self._watcher = None
                        return
                    done, tids = self._watching.popleft()
                done.block_until_ready()
                t = time.monotonic()
                with self._mu:
                    for tid in tids:
                        spans = self._spans.get(tid)
                        if spans is not None:
                            spans.setdefault("done", t)
        except Exception as e:
            with self._mu:
                self._watcher = None
            contained_crash("trace.done_watcher", e)

    # -- analysis (cold path) -----------------------------------------------
    def span_table(self) -> dict[int, dict]:
        with self._mu:
            return {tid: dict(spans) for tid, spans in self._spans.items()}

    def orphans(self) -> list[int]:
        """Admitted traces with no terminal span — each one is a leak in
        the pipeline's accounting (the K-shard propagation test pins
        this at zero after flush)."""
        with self._mu:
            return [tid for tid, spans in self._spans.items()
                    if "admission" in spans
                    and not any(t in spans for t in TERMINALS)]

    def latency_block(self) -> dict:
        """The artifact block: per-stage latency percentiles (ms) plus
        end-to-end wire-to-commit / wire-to-grad / wire-to-done (the
        fused path's headline), the sample rate, and
        the trace accounting (completed / shed / orphaned / overflow)."""
        table = self.span_table()
        stages: dict[str, list[float]] = {label: [] for label, _, _ in _PAIRS}
        completed = shed = 0
        for spans in table.values():
            if "shed" in spans:
                shed += 1
            elif "commit" in spans:
                completed += 1
            for label, a, b in _PAIRS:
                # b >= a: pipeline pairs are naturally ordered, except
                # deal/grad — a frame's first grad-after-commit can
                # predate a later RE-deal of the same slot, in which
                # case the deal span did not feed that grad and the
                # pair is causally mispaired, not a negative latency
                if a in spans and b in spans and spans[b] >= spans[a]:
                    stages[label].append(1e3 * (spans[b] - spans[a]))
        with self._mu:
            rate, overflow = self.sample_rate, self.overflow
        return {
            "unit": "ms",
            "sample_rate": rate,
            "stages": {label: percentile_summary(vals)
                       for label, vals in stages.items()},
            "wire_to_grad": percentile_summary(stages["wire_to_grad"]),
            "wire_to_done": percentile_summary(stages["wire_to_done"]),
            "n_traces": len(table),
            "completed": completed,
            "shed": shed,
            "orphans": len(self.orphans()),
            "overflow": overflow,
        }


# THE process-wide recorder (one receiver per process is the shipped
# topology). Senders never touch it — their trace state rides the wire.
RECORDER = TraceRecorder()


# -- program spans ------------------------------------------------------------
# Host spans at the learner's and the ingest plane's layer boundaries go
# to the profiler's own trace, beside the device's op line, whenever a
# profiler session is on, AND into the start-up log (``startup_log.LOG``:
# name, both ends on ``time.monotonic()``, thread, the span open round it,
# stats), profiler or none, until the span's name has been kept
# ``per_name`` times or the log has reached its bound: the log is how a
# process accounts for its start-up, and the spans a traced window's first
# chunks leave in both are what puts the log's clock and the profiler's on
# one axis. After that a span costs a compare and a set lookup more than
# before the log existed (a loop's names are past their share within its
# first sixteen chunks, so a steady state runs as it did without the log),
# and with no profiler on an annotation costs about a microsecond and
# stores nothing. ``startup.describe()`` installs
# ``jax.profiler.TraceAnnotation`` once the backend is up (this package
# imports no jax); processes that never start a backend (actors, unit
# tests) keep the log alone.


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set_metadata(self, **stats) -> None:
        """Stats known only inside the span (rows moved, time waited)."""


class _LoggedSpan:
    """A span kept in the start-up log and, where there is an annotator,
    handed to it as well (``inner``)."""

    __slots__ = ("_name", "_stats", "_inner", "_index")

    def __init__(self, name: str, stats: dict, inner):
        self._name, self._stats, self._inner = name, stats, inner
        self._index = -1

    def __enter__(self):
        self._index = _LOG.begin(self._name, self._stats)
        if self._inner is not None:
            self._inner.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._inner is not None:
            self._inner.__exit__(*exc)
        _LOG.end(self._index)

    def set_metadata(self, **stats) -> None:
        _LOG.annotate(self._index, stats)
        if self._inner is not None:
            self._inner.set_metadata(**stats)


NULL_SPAN = _NullSpan()
_annotator = None


def set_annotator(factory) -> None:
    """``factory(name, **stats)`` -> a context manager with
    ``set_metadata(**stats)``; ``None`` uninstalls. Also how a test
    records spans without a profiler."""
    global _annotator
    _annotator = factory


def span(name: str, **stats):
    inner = None if _annotator is None else _annotator(name, **stats)
    if _LOG.full or name in _LOG.closed:
        _LOG.dropped()
        return NULL_SPAN if inner is None else inner
    return _LoggedSpan(name, stats, inner)


# -- program table ------------------------------------------------------------
# name -> (jitted fn, abstract arguments), entered by the owner at the
# program's first dispatch. The arguments are ShapeDtypeStruct trees made
# by the caller, so nothing on the device is kept alive.
_PROGRAMS: dict[str, tuple] = {}


def register_program(name: str, fn, abstract_args: tuple) -> None:
    _PROGRAMS[name] = (fn, abstract_args)


def compiled_text(name: str) -> str:
    """The compiled HLO text of a registered program, every instruction
    with the ``op_name`` (named-scope path) it came from. Lowers and
    compiles on demand from the abstract arguments; only a trace reader
    calls it (``io/profiling.compiled_text_of`` says what it costs; jax
    lives there, not in this package)."""
    from d4pg_tpu.io.profiling import compiled_text_of

    fn, args = _PROGRAMS[name]
    return compiled_text_of(fn, args)
