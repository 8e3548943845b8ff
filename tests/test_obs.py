"""Observability plane (d4pg_tpu/obs): wire-to-grad trace spans, the
unified metrics registry, and the chaos flight recorder.

Tier-1 scope (marker ``obs``): registry consistency + provider
lifecycle, sink-crash containment in the metrics bus, the v2 codec's
trace header extension (round trip + eternal backward compatibility),
span propagation across the K-shard ordered merge under chaos
(monotone sequences, zero orphans, shed frames terminate), the
flight-recorder postmortem on an injected lock-hierarchy violation,
and the bench-artifact ``latency`` schema gate.
"""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from d4pg_tpu.obs import flight as obs_flight
from d4pg_tpu.obs import trace as obs_trace
from d4pg_tpu.obs.registry import REGISTRY, MetricsRegistry
from d4pg_tpu.replay.uniform import TransitionBatch

pytestmark = pytest.mark.obs

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch(rng, n, obs_dim=6, act_dim=2):
    return TransitionBatch(
        obs=rng.standard_normal((n, obs_dim)).astype(np.float32),
        action=rng.uniform(-1, 1, (n, act_dim)).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        next_obs=rng.standard_normal((n, obs_dim)).astype(np.float32),
        done=np.zeros(n, np.float32),
        discount=np.full(n, 0.99, np.float32),
    )


# ------------------------------------------------------------ registry ----

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    c = reg.counter("a.rows")
    assert reg.counter("a.rows") is c  # get-or-create is idempotent
    c.inc()
    c.inc(41)
    reg.gauge("a.rate").set(3.5)
    h = reg.histogram("a.lat")
    for v in (1.0, 2.0, 3.0, 100.0):
        h.observe(v)
    out = reg.export()
    assert out["counters"]["a.rows"] == 42
    assert out["gauges"]["a.rate"] == 3.5
    lat = out["histograms"]["a.lat"]
    assert lat["n"] == 4 and lat["p50"] == 2.5 and lat["p99"] > 90.0
    reg.reset_metrics()
    assert reg.export()["counters"]["a.rows"] == 0


def test_registry_provider_consistent_snapshot_and_weakref():
    reg = MetricsRegistry()

    class Owner:
        def __init__(self):
            self._mu = threading.Lock()
            self.n = 7

        def stats(self):
            with self._mu:  # the provider reads under its OWNING lock
                return {"n": self.n}

    o = Owner()
    reg.register_provider("owner", o.stats)
    assert reg.export()["owner"] == {"n": 7}
    # a dying owner drops out of export instead of leaking or raising
    del o
    assert "owner" not in reg.export()


def test_registry_unregister_only_evicts_own_slot():
    reg = MetricsRegistry()

    class Owner:
        def __init__(self, n):
            self.n = n

        def stats(self):
            return {"n": self.n}

    old, new = Owner(1), Owner(2)
    reg.register_provider("svc", old.stats)
    reg.register_provider("svc", new.stats)  # last-registered wins
    reg.unregister_provider("svc", old.stats)  # stale close: must NOT evict
    assert reg.export()["svc"] == {"n": 2}
    reg.unregister_provider("svc", new.stats)
    assert "svc" not in reg.export()


def test_registry_crashing_provider_contained():
    reg = MetricsRegistry()

    def bad():
        raise RuntimeError("boom")

    reg.register_provider("bad", bad)
    out = reg.export()
    assert "boom" in out["bad"]["provider_error"]


# --------------------------------------------- metrics-bus containment ----

def test_metrics_bus_poisoned_sink_disabled_not_fatal(capsys):
    from d4pg_tpu.io.metrics import MetricsBus

    class Poisoned:
        writes = 0

        def write(self, step, metrics):
            Poisoned.writes += 1
            raise IOError("disk full")

        def close(self):
            raise IOError("still broken")

    class Good:
        def __init__(self):
            self.rows = []

        def write(self, step, metrics):
            self.rows.append((step, dict(metrics)))

        def close(self):
            self.closed = True

    fails0 = REGISTRY.counter("metrics_bus.sink_failures").value
    good = Good()
    bus = MetricsBus(sinks=[Poisoned(), good])
    for step in range(3):
        bus.log(step, {"x": 1.0})  # must not raise
    # poisoned sink fired once, got disabled, the good sink kept logging
    assert Poisoned.writes == 1
    assert [s for s, _ in good.rows] == [0, 1, 2]
    bus.close()  # poisoned close contained too
    assert good.closed
    # every failure counted in the unified registry (write + close)
    assert REGISTRY.counter("metrics_bus.sink_failures").value == fails0 + 2
    assert "disabled" in capsys.readouterr().out


# ------------------------------------------------- v2 trace extension -----

def test_raw_codec_trace_extension_roundtrip(rng):
    from d4pg_tpu.distributed.transport import (
        decode_raw, encode_raw, raw_frame_meta, raw_frame_meta_ex)

    b = _batch(rng, 5)
    plain = encode_raw("a0", b, count_env_steps=False)[8:]  # strip frame hdr
    traced = encode_raw("a0", b, count_env_steps=False,
                        trace=(0xDEADBEEF, 123.456))[8:]
    # extension costs exactly 16 bytes and decodes to identical columns
    assert len(traced) == len(plain) + 16
    for enc in (plain, traced):
        aid, got, count = decode_raw(enc)
        assert aid == "a0" and count is False
        np.testing.assert_array_equal(got.obs, b.obs)
        np.testing.assert_array_equal(got.discount, b.discount)
    # header-only meta surfaces the trace without touching columns
    assert raw_frame_meta_ex(plain)[3] is None
    tid, ts = raw_frame_meta_ex(traced)[3]
    assert tid == 0xDEADBEEF and ts == pytest.approx(123.456)
    # the 3-tuple compatibility view is unchanged either way
    assert raw_frame_meta(traced) == ("a0", 5, False)


def test_trace_ids_unique_across_salts():
    a = {obs_trace.new_trace_id(1) for _ in range(100)}
    b = {obs_trace.new_trace_id(2) for _ in range(100)}
    assert len(a) == len(b) == 100 and not (a & b)


# ------------------------------------------------------ trace recorder ----

def test_trace_recorder_spans_and_latency_block():
    rec = obs_trace.TraceRecorder()
    rec.enable(0.5)
    t0 = time.monotonic()
    rec.begin(1, t0)
    for stage in ("admission", "decode", "stage", "merge"):
        rec.record_span(1, stage)
    rec.mark_committed([1])
    assert rec.orphans() == []  # commit is terminal
    rec.mark_grad()
    rec.begin(2, t0)
    rec.record_span(2, "admission")
    assert rec.orphans() == [2]  # admitted, not yet terminated
    rec.terminal_shed(2)
    assert rec.orphans() == []
    block = rec.latency_block()
    assert block["sample_rate"] == 0.5
    assert block["completed"] == 1 and block["shed"] == 1
    assert block["wire_to_grad"]["n"] == 1
    assert block["stages"]["commit_to_grad"]["n"] == 1
    # stage order sanity inside the one completed trace
    spans = rec.span_table()[1]
    order = [spans[s] for s in
             ("send", "admission", "decode", "stage", "merge", "commit",
              "grad")]
    assert order == sorted(order)


def test_trace_recorder_bounded_and_disabled_noop():
    rec = obs_trace.TraceRecorder(max_traces=4)
    rec.enable(1.0)
    for tid in range(4):
        rec.begin(tid, 0.0)  # all live (no terminal): table is full
    rec.begin(99, 0.0)
    assert rec.overflow == 1 and 99 not in rec.span_table()
    rec.terminal_shed(0)  # now one record is evictable
    rec.begin(100, 0.0)
    assert 100 in rec.span_table() and 0 not in rec.span_table()
    rec.disable()
    rec.begin(101, 0.0)
    assert 101 not in rec.span_table()  # disabled recorder records nothing


# ------------------------------ K-shard propagation under chaos (sat.) ----

def test_trace_propagation_k2_merge_under_chaos():
    """Every sampled trace crossing the K=2 sharded ordered merge under
    the full chaos mix must keep a monotone span sequence (admission <=
    decode <= stage <= merge <= commit) and terminate — shed frames get
    terminal ``shed`` spans, nothing leaks (zero orphans)."""
    from d4pg_tpu.fleet import ChaosConfig, FleetConfig, FleetHarness

    chaos = ChaosConfig(
        drop_prob=0.1, delay_prob=0.2, delay_min_s=0.001, delay_max_s=0.005,
        crash_prob=0.05, restart_delay_s=0.3,
        receiver_stall_s=0.1, stall_every_s=0.4, seed=7)
    cfg = FleetConfig(
        n_actors=8, max_ticks=12, rows_per_sec=400.0, block_rows=16,
        obs_dim=24, act_dim=4, capacity=20_000, heartbeat_timeout=0.5,
        evict_every_s=0.1, send_timeout=0.5, chaos=chaos,
        ingest_shards=2, trace_sample=1.0)
    result = FleetHarness(cfg).run()
    assert result["deadlocks"] == 0
    assert result["frames_traced"] > 20  # sampling actually ran
    lat = result["latency"]
    assert lat is not None and lat["orphans"] == 0
    table = obs_trace.RECORDER.span_table()
    assert len(table) == result["frames_traced"] >= lat["completed"] > 0
    ordered_stages = ("send", "admission", "decode", "stage", "merge",
                      "commit", "grad")
    completed = shed = 0
    for tid, spans in table.items():
        terminal = [t for t in ("commit", "grad", "shed") if t in spans]
        assert terminal, f"trace {tid} leaked with spans {sorted(spans)}"
        if "shed" in spans:
            shed += 1
            continue
        completed += 1
        # committed traces crossed EVERY stage, in monotone order
        ts = [spans[s] for s in ordered_stages if s in spans]
        assert len(ts) >= 6
        assert ts == sorted(ts), f"non-monotone spans for {tid}: {spans}"
    assert completed == lat["completed"] and shed == lat["shed"]


def test_trace_tombstoned_frames_get_terminal_shed_spans(rng):
    """Deterministic tombstone coverage: undecodable-but-admissible v2
    frames (good header, truncated columns) are admitted with a trace,
    tombstoned by the shard worker, and must end in a terminal ``shed``
    span — never an orphan — while interleaved valid frames commit."""
    from d4pg_tpu.distributed.replay_service import ReplayService
    from d4pg_tpu.distributed.transport import encode_raw
    from d4pg_tpu.replay.uniform import ReplayBuffer

    obs_trace.RECORDER.reset()
    obs_trace.RECORDER.enable(1.0)
    svc = ReplayService(ReplayBuffer(10_000, 6, 2), num_ingest_shards=2)
    good_tids, bad_tids = [], []
    try:
        for i in range(12):
            tid = obs_trace.new_trace_id(3)
            frame = encode_raw(f"lane-{i % 2}", _batch(rng, 4),
                               trace=(tid, time.monotonic()))[8:]
            if i % 3 == 2:
                frame = frame[:-7]  # truncate mid-column: decode raises
                bad_tids.append(tid)
            else:
                good_tids.append(tid)
            assert svc.add_payload(frame, shard=i % 2, codec="raw")
        svc.flush(timeout=10.0)
        table = obs_trace.RECORDER.span_table()
        for tid in bad_tids:
            assert "shed" in table[tid], table[tid]
            assert "commit" not in table[tid]
        for tid in good_tids:
            assert "commit" in table[tid], table[tid]
        assert obs_trace.RECORDER.orphans() == []
        assert svc.ingest_stats()["decode_errors"] == len(bad_tids)
    finally:
        obs_trace.RECORDER.disable()
        svc.close()


def test_trace_shed_frames_get_terminal_spans(rng):
    """Deterministic watermark-shed coverage: with the workers frozen,
    admissions past the shed watermark evict the oldest queued frames —
    each evicted trace must get its terminal ``shed`` span at eviction
    time (the zero-leak contract), not linger half-recorded."""
    from d4pg_tpu.distributed.replay_service import ReplayService
    from d4pg_tpu.distributed.transport import encode_raw
    from d4pg_tpu.replay.uniform import ReplayBuffer

    obs_trace.RECORDER.reset()
    obs_trace.RECORDER.enable(1.0)
    svc = ReplayService(ReplayBuffer(10_000, 6, 2), ingest_capacity=4,
                        shed_watermark=0.5, num_ingest_shards=2)
    # freeze the plane: workers and commit exit, admissions still run
    svc._stop.set()
    for w in svc._workers:
        w.join(timeout=5.0)
    svc._commit_thread.join(timeout=5.0)
    tids = []
    for i in range(6):  # shard 0 only; shed_at = 2 -> 4 evictions
        tid = obs_trace.new_trace_id(4)
        tids.append(tid)
        frame = encode_raw("lane-0", _batch(rng, 4),
                           trace=(tid, time.monotonic()))[8:]
        assert svc.add_payload(frame, shard=0, codec="raw")
    table = obs_trace.RECORDER.span_table()
    shed = [tid for tid in tids if "shed" in table[tid]]
    queued = [tid for tid in tids if "shed" not in table[tid]]
    assert len(shed) == 4 and len(queued) == 2  # oldest evicted, FIFO
    assert shed == tids[:4]
    for tid in shed:
        assert "admission" in table[tid]  # admitted first, then evicted
    stats = svc.ingest_stats()
    assert stats["sheds"] == 4 and stats["shed_rows"] == 16
    obs_trace.RECORDER.disable()
    with svc._lock:
        svc._pending = 0  # frozen plane: skip close()'s flush deadline


# --------------------- the fused path: positions, h2d, land, grad, done ----

def test_mark_grad_landed_stamps_only_what_has_landed():
    rec = obs_trace.TraceRecorder()
    rec.enable(1.0)
    for tid, through in ((1, 10), (2, 20), (3, None)):
        rec.begin(tid, time.monotonic())
        rec.record_span(tid, "admission")
        rec.mark_committed([tid], through=through)
    assert rec.mark_grad(landed=5) == 0  # nothing has landed
    rec.mark_through("h2d", 10)
    rec.mark_through("land", 10)
    assert rec.mark_grad(landed=10) == 1
    table = rec.span_table()
    assert {"h2d", "land", "grad"} <= set(table[1])
    assert not {"h2d", "land", "grad"} & set(table[2])
    # a trace with no position (a buffer that keeps none) waits for nothing,
    # but keeps its place in line behind the one whose rows are in flight
    assert rec.mark_grad(landed=10) == 0 and "grad" not in table[3]
    assert rec.mark_grad(landed=20) == 2
    table = rec.span_table()
    assert "grad" in table[2] and "grad" in table[3]
    assert "land" not in table[2]  # its block was never said to land
    # the bare call keeps its meaning: everything pending
    rec.begin(4, time.monotonic())
    rec.mark_committed([4], through=99)
    assert rec.mark_grad() == 1
    # a trace committed after its block moved on takes the block's stamps
    rec.begin(5, time.monotonic())
    rec.mark_committed([5], through=7)
    late = rec.span_table()[5]
    assert late["h2d"] == table[1]["h2d"] and late["land"] == table[1]["land"]
    block = rec.latency_block()
    assert block["stages"]["h2d_to_land"]["n"] == 2
    assert block["stages"]["land_to_grad"]["n"] == 1
    assert block["wire_to_done"]["n"] == 0 and rec._watcher is None


@pytest.fixture
def fused_plane(rng):
    """``ReplayService`` -> ``FusedDeviceReplay`` -> ``FusedLoop`` at tiny
    sizes, compiled, with the process-wide recorder armed."""
    from test_program_spans import BLOCK, build_plane, rows

    loop, service, buf, state = build_plane(rng, fill=2 * BLOCK)
    state, _m = loop.run(state, 2)
    obs_trace.RECORDER.reset()
    obs_trace.RECORDER.enable(1.0)

    def traced_add(n=BLOCK):
        from d4pg_tpu.distributed.transport import encode_raw

        tid = obs_trace.new_trace_id(9)
        frame = encode_raw("lane-0", rows(rng, n),
                           trace=(tid, time.monotonic()))[8:]
        assert service.add_payload(frame, shard=0, codec="raw")
        service.flush(timeout=10.0)
        return tid

    yield loop, service, buf, state, traced_add
    obs_trace.RECORDER.disable()
    obs_trace.RECORDER.reset()
    loop.close()
    service.close()


def _watchers():
    return [t for t in threading.enumerate() if t.name == "trace-done"]


def test_fused_grad_waits_for_the_block_that_carries_the_rows(fused_plane):
    """Rows that arrive while chunk 0 runs are staged after chunk 1's
    dispatch and committed before chunk 2's: chunk 1 cannot sample them and
    must not stamp ``grad`` (it did before PR 36); chunk 2 does, and the
    chunk's end on the device is ``done``."""
    import jax

    loop, _service, buf, state, traced_add = fused_plane
    tids, seen, own = [], [], []

    def on_chunk(st, _k):
        if not tids:
            tids.append(traced_add())
        seen.append(dict(obs_trace.RECORDER.span_table()[tids[0]]))
        jax.block_until_ready(st)  # the test's own stamp of the chunk's end
        own.append(time.monotonic())

    state, _m = loop.run(state, 8, on_chunk=on_chunk)
    assert {"commit"} <= set(seen[0]) and "h2d" not in seen[0]
    # chunk 1: the block is on its way (h2d), not landed: no grad
    assert "h2d" in seen[1] and not {"land", "grad"} & set(seen[1])
    # chunk 2: landed by its ingest.commit, consumed by its dispatch
    assert {"land", "grad"} <= set(seen[2])
    for _ in range(200):  # the watcher stamps `done` on its own thread
        spans = obs_trace.RECORDER.span_table()[tids[0]]
        if "done" in spans:
            break
        time.sleep(0.01)
    order = [spans[s] for s in ("send", "admission", "decode", "stage",
                                "merge", "commit", "h2d", "land", "grad",
                                "done")]
    assert order == sorted(order)
    # `done` is the end of chunk 2: beside the test's own block_until_ready
    # stamp of that chunk, give or take the time a thread takes to wake on a
    # machine that runs six test workers (on the chip: 0.19 ms, PERF.md)
    assert spans["grad"] <= spans["done"] and abs(spans["done"] - own[2]) < 0.1
    assert buf.landed == buf.staged_position()[0]
    block = obs_trace.RECORDER.latency_block()
    for pair in ("commit_to_h2d", "h2d_to_land", "land_to_grad",
                 "grad_to_done", "wire_to_grad", "wire_to_done"):
        assert block["stages"][pair]["n"] == 1, pair
    assert block["wire_to_done"]["p95"] >= block["wire_to_grad"]["p95"]
    assert block["orphans"] == 0
    for _ in range(200):  # nothing pending: the watcher has left
        if not _watchers():
            break
        time.sleep(0.01)
    assert not _watchers() and obs_trace.RECORDER._watcher is None


def test_no_watcher_thread_while_the_recorder_is_disabled(fused_plane):
    loop, service, _buf, state, _traced_add = fused_plane
    from test_program_spans import BLOCK, rows

    obs_trace.RECORDER.disable()
    fed = []

    def on_chunk(_st, _k):
        assert not _watchers()
        if len(fed) < 2:
            fed.append(service.add(rows(np.random.default_rng(1), BLOCK)))
            service.flush()

    state, _m = loop.run(state, 8, on_chunk=on_chunk)
    assert fed == [True, True] and not _watchers()
    assert obs_trace.RECORDER._watcher is None
    assert not obs_trace.RECORDER._await_grad  # nothing was ever pending
    # armed, but with no traced frame pending: still no thread
    obs_trace.RECORDER.enable(1.0)
    state, _m = loop.run(state, 4, on_chunk=lambda s, k: None)
    assert not _watchers() and obs_trace.RECORDER.span_table() == {}


def test_zero_orphans_after_a_staging_drop(fused_plane):
    """Three traced blocks into a two-block staging ring before the learner
    stages any: the first frame's rows are dropped, its trace ends in a
    terminal ``shed`` and is never stamped ``grad``; the others complete."""
    loop, service, _buf, state, traced_add = fused_plane
    tids = [traced_add() for _ in range(3)]
    table = obs_trace.RECORDER.span_table()
    assert "shed" in table[tids[0]] and "commit" in table[tids[0]]
    assert all("shed" not in table[t] for t in tids[1:])
    state, _m = loop.run(state, 6)
    for _ in range(200):
        table = obs_trace.RECORDER.span_table()
        if all("done" in table[t] for t in tids[1:]):
            break
        time.sleep(0.01)
    assert not {"h2d", "land", "grad", "done"} & set(table[tids[0]])
    for t in tids[1:]:
        assert {"h2d", "land", "grad", "done"} <= set(table[t])
        assert table[t]["land"] <= table[t]["grad"] <= table[t]["done"]
    block = obs_trace.RECORDER.latency_block()
    assert block["orphans"] == 0 and block["shed"] == 1
    assert block["wire_to_done"]["n"] == 2
    assert service.ingest_stats()["rows_dropped"] >= 16


# ----------------------------------------------------- flight recorder ----

@pytest.mark.failflow
def test_contained_crash_counts_and_flight_records():
    """The shared thread-top-frame containment helper: one counter bump
    on ``threads.contained_crashes`` plus one flight event carrying the
    role and the exception — the breadcrumb every wrapped plane thread
    leaves instead of dying silently."""
    from d4pg_tpu.obs.containment import contained_crash

    ctr = REGISTRY.counter("threads.contained_crashes")
    before = ctr.value
    obs_flight.RECORDER.reset()
    contained_crash("test.lane", ValueError("boom"))
    assert ctr.value == before + 1
    events = [e for e in obs_flight.RECORDER.events()
              if e["kind"] == "thread_crash_contained"]
    assert events and events[-1]["role"] == "test.lane"
    assert events[-1]["error"] == "ValueError: boom"


def test_flight_recorder_ring_bounded_and_dump(tmp_path):
    rec = obs_flight.FlightRecorder(maxlen=8)
    for i in range(20):
        rec.record("tick", i=i)
    assert len(rec) == 8
    events = rec.events()
    assert [e["i"] for e in events] == list(range(12, 20))  # newest kept
    assert all(e["kind"] == "tick" and "t" in e and "seq" in e
               for e in events)
    path = rec.dump(str(tmp_path), "unit test!", extra={"n": 1})
    with open(path) as f:
        d = json.load(f)
    assert d["reason"] == "unit test!" and d["n_events"] == 8
    assert d["context"] == {"n": 1}
    assert [e["i"] for e in d["events"]] == list(range(12, 20))


def test_flight_dump_on_injected_lock_violation(tmp_path):
    """Acceptance bar: an injected lock-hierarchy violation (record
    mode) during a chaos smoke produces a flight-recorder dump that
    contains the violation event AND the >=32 events preceding it."""
    from d4pg_tpu.core import locking
    from d4pg_tpu.fleet import ChaosConfig, FleetConfig, FleetHarness

    chaos = ChaosConfig(
        drop_prob=0.1, delay_prob=0.2, delay_min_s=0.001, delay_max_s=0.005,
        crash_prob=0.05, restart_delay_s=0.3, seed=7)
    cfg = FleetConfig(
        n_actors=8, max_ticks=16, rows_per_sec=400.0, block_rows=16,
        obs_dim=24, act_dim=4, capacity=20_000, heartbeat_timeout=0.5,
        evict_every_s=0.1, send_timeout=0.5, chaos=chaos,
        flight_dir=str(tmp_path))

    obs_flight.RECORDER.reset()  # stale events must not trip the gate

    def inject():
        # wait until THIS run armed record mode and produced a preamble
        # of ring events, then commit the PR-4 wedge shape: a
        # service-tier acquisition under a shard-tier hold (record
        # mode: counted, not raised)
        deadline = time.monotonic() + 20.0
        while ((not locking.debug_enabled()
                or len(obs_flight.RECORDER) < 40)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        leaf = locking.TieredLock("shard")
        outer = locking.TieredLock("service")
        with leaf:
            with outer:
                pass

    t = threading.Thread(target=inject, daemon=True)
    t.start()
    result = FleetHarness(cfg).run()
    t.join(timeout=25.0)
    assert result["locks"]["hierarchy_violations"] == 1
    assert result["deadlocks"] == 0
    dump = result["flight_dump"]
    assert dump is not None and os.path.exists(dump)
    with open(dump) as f:
        d = json.load(f)
    assert d["reason"] == "hierarchy_violation"
    kinds = [e["kind"] for e in d["events"]]
    assert "lock_violation" in kinds
    idx = kinds.index("lock_violation")
    assert idx >= 32, f"only {idx} events precede the violation"
    assert "acquiring 'service'" in d["events"][idx]["msg"]
    # the preamble is real plane activity, not padding
    assert kinds.count("admit") >= 32


def test_clean_smoke_produces_no_dump(tmp_path):
    from d4pg_tpu.fleet import ChaosConfig, FleetConfig, FleetHarness

    cfg = FleetConfig(
        n_actors=2, max_ticks=4, rows_per_sec=400.0, block_rows=16,
        obs_dim=24, act_dim=4, capacity=20_000, heartbeat_timeout=0.5,
        evict_every_s=0.1, send_timeout=0.5, chaos=ChaosConfig(seed=1),
        flight_dir=str(tmp_path))
    result = FleetHarness(cfg).run()
    assert result["deadlocks"] == 0
    assert result["flight_dump"] is None
    assert glob.glob(os.path.join(str(tmp_path), "*.json")) == []


# ------------------------------------------ bench-artifact schema gate ----

_LATENCY_STAGES = ("wire_to_admission", "admission_to_decode",
                   "decode_to_stage", "stage_to_merge", "merge_to_commit",
                   "commit_to_grad", "wire_to_commit", "wire_to_grad")
_OVERHEAD_KEYS = {"rows_per_sec_traced", "rows_per_sec_untraced",
                  "rows_loss_pct", "hook_ns_per_chunk", "sample_rate"}


def test_fleet_artifact_latency_schema():
    """The newest committed ``docs/evidence/fleet`` artifact must carry
    the ``latency`` block with per-stage p50/p95/p99 histograms, the
    end-to-end wire-to-grad series, the sampling rate, and the measured
    tracing-overhead figures — a later PR that drops any of it fails
    tier-1 here instead of silently shipping a blind artifact."""
    arts = sorted(glob.glob(os.path.join(
        REPO_ROOT, "docs", "evidence", "fleet", "fleet_*.json")))
    assert arts, "no committed fleet artifact"
    with open(arts[-1]) as f:  # stamp-named: lexical order = newest last
        artifact = json.load(f)
    lat = artifact.get("latency")
    assert lat, "newest fleet artifact lost its latency block"
    assert lat["sample_rate"] > 0
    assert lat["n_traces"] > 0 and lat["orphans"] == 0
    for stage in _LATENCY_STAGES:
        h = lat["stages"][stage]
        assert {"p50", "p95", "p99", "n"} <= set(h), stage
    assert lat["wire_to_grad"]["n"] > 0
    assert _OVERHEAD_KEYS <= set(lat["overhead"])
    # the acceptance bound: <= 2% throughput loss at the default rate
    assert lat["overhead"]["rows_loss_pct"] is not None
    assert lat["overhead"]["rows_loss_pct"] <= 2.0
    # the shard-sweep scaling table carries stage attribution next to
    # lock_wait_ms on every traced (K>=2) row
    for row in artifact["shard_sweep"]["scaling"]:
        assert "stage_ms" in row and "lock_wait_ms" in row
        if row["ingest_shards"] > 1:
            assert row["stage_ms"] is not None
            assert "wire_to_commit" in row["stage_ms"]


# ------------------------------------------------- registry end-to-end ----

def test_registry_export_covers_live_planes():
    """One export() answers for every plane at once: the lock provider
    is always present, a live ReplayService's ingest snapshot appears
    under 'ingest' and drops out after close()."""
    from d4pg_tpu.distributed.replay_service import ReplayService
    from d4pg_tpu.replay.uniform import ReplayBuffer

    svc = ReplayService(ReplayBuffer(1000, 6, 2), num_ingest_shards=2)
    try:
        rng = np.random.default_rng(0)
        svc.add(_batch(rng, 8), actor_id="a0", shard=0)
        svc.flush()
        out = REGISTRY.export()
        assert out["locks"]["hierarchy_violations"] >= 0
        assert out["ingest"]["rows_committed"] >= 8
        assert out["ingest"]["num_ingest_shards"] == 2
        assert out["counters"]["ingest.rows_committed"] >= 8
    finally:
        svc.close()
    assert "ingest" not in REGISTRY.export()


def test_registry_export_covers_weight_plane():
    """The weight plane registers an aggregate 'weights' provider: the
    block is always present (module-lifetime registration, mirroring
    'locks'), counts live servers, and folds per-server frame/byte/
    oracle tallies plus the staleness histogram."""
    from d4pg_tpu.distributed.weight_plane import WeightPlaneServer
    from d4pg_tpu.distributed.weights import WeightStore

    base = REGISTRY.export()["weights"]
    assert "staleness_ms" in base
    store = WeightStore()
    srv = WeightPlaneServer(store)
    try:
        out = REGISTRY.export()["weights"]
        assert out["servers"] >= base.get("servers", 0) + 1
        assert "snapshots_built" in out
        assert "delta_hit_rate" in out
    finally:
        srv.close()


def test_registry_export_covers_serving_plane():
    """A live PolicyInferenceServer registers the 'serving' provider
    (queue depth, batch occupancy/latency histograms, the staleness-SLA
    pair) and unregisters it on close — per-instance lifetime, like
    'ingest', not module-lifetime like 'weights'."""
    from d4pg_tpu.distributed.weights import WeightStore
    from d4pg_tpu.learner.state import D4PGConfig
    from d4pg_tpu.serving import PolicyInferenceServer

    cfg = D4PGConfig(obs_dim=4, act_dim=2, n_atoms=11, hidden=(16,))
    srv = PolicyInferenceServer(cfg, WeightStore())
    try:
        out = REGISTRY.export()["serving"]
        assert out["queue_depth"] == 0
        assert out["sla_staleness_s"] == srv.sla_staleness_s
        for block in ("batch_occupancy", "batch_rows", "latency_ms"):
            assert "p95" in out[block]
        for counter in ("requests", "batches", "adoptions",
                        "fenced_rejected", "sla_breaches"):
            assert counter in out
    finally:
        srv.close()
    assert "serving" not in REGISTRY.export()
