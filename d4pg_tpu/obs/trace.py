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

    send ──> admission ──> decode ──> stage ──> merge ──> commit ──> grad
                 │             │                  │
                 └── shed ─────┴──── shed ────────┘   (terminal: counted,
                                                       never leaked)

- ``admission``  — the frame entered an ingest shard's deque
  (``ReplayService.add_payload``; zero-decode for v2 frames).
- ``decode``     — the shard worker parsed the columns.
- ``stage``      — rows staged (direct-stage ring copy, or handed to
  the ordered-merge inbox on the non-fused path).
- ``merge``      — the commit thread popped the ticket in global order.
- ``commit``     — rows landed in replay state (buffer insert /
  direct-stage accounting settled). On the FUSED path "replay state" is
  the HOST staging ring (``FusedDeviceReplay.add``): the rows reach the
  device ring one ``fused.stage_block`` and one ``fused.commit_staged``
  later, i.e. one to two chunks after this stamp.
- ``grad``       — first learner consumption after commit: the fused
  loop marks it right after each chunk dispatch
  (``learner/loop.FusedLoop.run``), the fleet harness's consumer lane
  marks it after each concurrent ``sample()``. The stamp is a DISPATCH,
  not a completion, and it is not gated on the block that carries the
  rows. On the fused path ``wire_to_grad`` is therefore short by one to
  two chunks (the staged block's wait for its commit) plus one chunk's
  run time on the device; the host-sampled paths are short by the
  dispatch-to-completion time only. The repair (gate ``grad`` on the
  ``block=`` that ``fused.commit_staged`` lands, stamp at the chunk's
  completion) is entered in PERF.md section 7 against the
  ``humanoid-mlp.learn-fleet-tcp`` cell, the first that would read it;
  the fleet harnesses and two lint families depend on the present stamps.

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

Program spans and the program table (below the recorder) are a separate,
stateless mechanism: ``span()`` hands the learner's and the ingest
plane's own boundaries to the profiler (the profiler is the span store;
nothing is kept here), and the table lets a trace reader turn a
``jax.named_scope`` inside a compiled program into device time.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import OrderedDict, deque

from d4pg_tpu.obs.registry import percentile_summary

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
          "grad")
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
    ("wire_to_commit", "send", "commit"),
    ("wire_to_grad", "send", "grad"),
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
        self._await_grad: deque = deque()
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

    def mark_committed(self, tids) -> None:
        """Commit spans for a merged group + queue them for the next
        grad-consumption mark."""
        if not self.enabled:
            return
        t = time.monotonic()
        with self._mu:
            for tid in tids:
                spans = self._spans.get(tid)
                if spans is not None and "commit" not in spans:
                    spans["commit"] = t
                    self._await_grad.append(tid)

    def mark_grad(self, ts: float | None = None) -> int:
        """Stamp every commit-pending trace with grad-consumption time.
        Called by the learner right after a fused-chunk dispatch (and by
        the fleet harness's consumer lane after each concurrent sample).
        Near-free when nothing is pending (one unlocked emptiness probe,
        benign race under the GIL)."""
        if not self._await_grad:
            return 0
        t = time.monotonic() if ts is None else ts
        n = 0
        with self._mu:
            while self._await_grad:
                tid = self._await_grad.popleft()
                spans = self._spans.get(tid)
                if spans is not None and "grad" not in spans:
                    spans["grad"] = t
                    n += 1
        return n

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
        end-to-end wire-to-commit / wire-to-grad, the sample rate, and
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
# profiler session is on; with none on, an annotation costs about a
# microsecond and stores nothing. ``startup.describe()`` installs
# ``jax.profiler.TraceAnnotation`` once the backend is up (this package
# imports no jax); processes that never start a backend (actors, unit
# tests) get the shared null span.


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set_metadata(self, **stats) -> None:
        """Stats known only inside the span (rows moved, time waited)."""


NULL_SPAN = _NullSpan()
_annotator = None


def set_annotator(factory) -> None:
    """``factory(name, **stats)`` -> a context manager with
    ``set_metadata(**stats)``; ``None`` uninstalls. Also how a test
    records spans without a profiler."""
    global _annotator
    _annotator = factory


def span(name: str, **stats):
    return NULL_SPAN if _annotator is None else _annotator(name, **stats)


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
