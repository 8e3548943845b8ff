"""Observability plane: wire-to-grad trace spans, the start-up log, the
unified metrics registry, and the chaos flight recorder.

Six stdlib-only modules (nothing here may import jax — the plane must
be importable from the transport/locking layers that run before any
backend exists):

- ``obs.registry`` — ONE process-wide registry of named counters/gauges/
  histograms plus *snapshot providers* (callables that produce a
  consistent dict under their own locks — the PR-4 rule that every
  counter is read under the lock that writes it). ``replay_service``,
  ``fused_buffer``, ``core.locking``, ``ReshardSentinel`` and the fleet
  harness all publish here; the bespoke
  ``*_stats()`` dicts survive as thin views over the same snapshots.
- ``obs.startup_log`` — a bounded log of where a process's start goes
  (``LOG``; always on, no switch): opened on the first line of the package,
  so it exists before ``import jax`` and can time it. It keeps every program
  span until the span's name has had its share (16) or the log is full, books first imports by package and self time
  through one hook that leaves at the chunk program's first dispatch, and
  takes ``jax.monitoring``'s compile-pipeline events from the listeners
  ``startup.configure`` installs (jax lives there, not here). ``train``
  prints its phases; a traced benchmark run reads them under ``setup_s``.
- ``obs.trace`` — program spans (``span()``: the learner's and the ingest
  plane's own boundaries, handed to the profiler's trace and kept in the
  start-up log) and the program
  table (a named scope inside a compiled program -> device time); and
  sampled per-frame trace spans riding the v2 wire
  codec's header extension: birth timestamp at the actor's socket
  write, span timestamps at admission, decode, stage, merge-pop,
  commit and grad-step consumption, aggregated into per-stage latency
  histograms with end-to-end wire-to-grad as the headline series.
- ``obs.flight`` — a bounded in-memory ring of recent structured
  events (admissions, sheds, evictions, order-breaks, lock-hierarchy
  violations, retries) the fleet harness dumps to
  ``docs/evidence/fleet/`` on deadlock, crash or assertion, so a chaos
  failure comes with a postmortem instead of a stack trace.
- ``obs.containment`` — the one-call crash-containment breadcrumb every
  thread role's top frame uses (``threads.contained_crashes`` counter +
  a flight event); jaxlint family 16 enforces its presence statically.
- ``obs.draw_ledger`` — the runtime twin of the rnggraph determinism
  pass (jaxlint families 22-24): per-stream RNG draw-call counts behind
  a transparent Generator proxy, exported as a canonical digest the A/B
  chaos drivers pin across arms ("equal seeded offered load" as an
  oracle, not an argument).

Lock discipline: every lock in this package is named ``_mu`` — a plain
``threading.Lock`` OUTSIDE the tiered hierarchy, deliberately terminal:
no code path holding an ``_mu`` acquires any other lock, so the
observability plane can be called from under any tiered lock without
adding an edge the lock graph could cycle through.
"""

from d4pg_tpu.obs import (containment, draw_ledger, flight, registry,
                          startup_log, trace)
from d4pg_tpu.obs.containment import contained_crash
from d4pg_tpu.obs.draw_ledger import LEDGER, DrawLedger
from d4pg_tpu.obs.flight import FlightRecorder, record_event
from d4pg_tpu.obs.registry import REGISTRY, MetricsRegistry
from d4pg_tpu.obs.trace import DEFAULT_SAMPLE, TraceRecorder

__all__ = [
    "containment", "draw_ledger", "flight", "registry", "startup_log",
    "trace",
    "FlightRecorder", "record_event", "contained_crash",
    "REGISTRY", "MetricsRegistry",
    "DEFAULT_SAMPLE", "TraceRecorder",
    "LEDGER", "DrawLedger",
]
