"""Program-side tracing (PR 25): the host spans the fused loop and the ingest
handoff open through ``obs.trace.span``, the named scopes inside the chunk
and commit programs as the program table's compiled text shows them, and
the counter of rows the host staging rings discard. CPU, tiny sizes."""

import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from d4pg_tpu.obs import trace
from d4pg_tpu.obs.registry import REGISTRY
from d4pg_tpu.replay.uniform import TransitionBatch

OBS, ACT, BLOCK, CAP = 5, 2, 16, 64
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Recorder:
    """An annotator that keeps what the profiler would: name, stats, depth
    and parent per thread, in opening order."""

    def __init__(self):
        self.spans = []
        self._open = threading.local()

    def __call__(self, name, **stats):
        return _Recorded(self, name, stats)


class _Recorded:
    def __init__(self, rec, name, stats):
        self.rec, self.name, self.stats = rec, name, dict(stats)

    def __enter__(self):
        stack = self.rec._open.__dict__.setdefault("stack", [])
        self.parent = stack[-1].name if stack else None
        self.depth = len(stack)
        stack.append(self)
        self.rec.spans.append(self)
        return self

    def __exit__(self, *exc):
        self.rec._open.stack.pop()

    def set_metadata(self, **stats):
        self.stats.update(stats)


@pytest.fixture
def recorder():
    rec = Recorder()
    trace.set_annotator(rec)
    yield rec
    trace.set_annotator(None)


def rows(rng, n, first=0):
    return TransitionBatch(
        obs=rng.standard_normal((n, OBS)).astype(np.float32),
        action=rng.uniform(-1, 1, (n, ACT)).astype(np.float32),
        reward=rng.standard_normal(n).astype(np.float32),
        next_obs=rng.standard_normal((n, OBS)).astype(np.float32),
        done=(first + np.arange(n)).astype(np.float32),
        discount=np.full(n, 0.99, np.float32))


def build_plane(rng, fill=CAP):
    """``ReplayService`` -> ``FusedDeviceReplay`` (a two-block staging ring,
    ``fill`` seeded rows drained to the device) -> ``FusedLoop``, and a fresh
    state: ``(loop, service, buffer, state)``. Close the loop, then the
    service."""
    import jax

    from d4pg_tpu.distributed.replay_service import ReplayService
    from d4pg_tpu.learner import D4PGConfig, init_state
    from d4pg_tpu.learner.loop import FusedLoop
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay

    config = D4PGConfig(obs_dim=OBS, act_dim=ACT, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16))
    buf = FusedDeviceReplay(CAP, OBS, ACT, alpha=0.6, block_rows=BLOCK,
                            staging_blocks=2)
    buf.add(rows(rng, fill))
    buf.drain()
    service = ReplayService(buf)
    loop = FusedLoop(config, buf, k=2, batch_size=8, service=service)
    return loop, service, buf, init_state(config, jax.random.key(0))


@pytest.fixture
def loop_and_service(rng):
    loop, service, _buf, state = build_plane(rng)
    yield loop, service, state
    loop.close()
    service.close()


def test_loop_spans_in_order_nested_and_sharing_chunk_and_block(
        recorder, loop_and_service, rng):
    loop, service, state = loop_and_service
    assert service.add(rows(rng, BLOCK, first=1000))
    service.flush()
    calls = []
    state, _m = loop.run(state, 2, on_chunk=lambda s, k: calls.append(k))
    # the leading flush landed the block; stage another for the handoff
    assert service.add(rows(rng, BLOCK, first=2000))
    service.flush()
    recorder.spans.clear()
    state, _m = loop.run(state, 6, on_chunk=lambda s, k: calls.append(k))
    learner = [s for s in recorder.spans
               if s.name.split(".")[0] in ("learner", "fused")
               or s.name in ("ingest.commit", "ingest.stage",
                             "ingest.lock_wait")]
    names = [s.name for s in learner]
    # run > flush (which drains the staged block: stage + commit) ...
    assert names[:2] == ["learner.run", "learner.flush"]
    flush = learner[1]
    assert flush.parent == "learner.run" and flush.stats["rows"] == BLOCK
    # ... then three chunks, each nested as the loop is written
    per_chunk = ["learner.chunk", "ingest.commit", "ingest.lock_wait",
                 "learner.dispatch", "ingest.stage", "ingest.lock_wait",
                 "learner.on_chunk"]
    first = names.index("learner.chunk")
    assert [n for n in names[first:] if not n.startswith("fused.")] \
        == per_chunk * 3
    chunks = [s for s in learner if s.name == "learner.chunk"]
    assert [c.stats["chunk"] for c in chunks] == [1, 2, 3]
    assert all(c.parent == "learner.run" and c.stats["k"] == 2
               for c in chunks)
    for s in learner[first:]:
        if s.name in ("learner.dispatch", "learner.on_chunk",
                      "ingest.commit", "ingest.stage"):
            assert s.parent == "learner.chunk" and s.depth == 2
        if s.name == "ingest.lock_wait":
            assert s.parent in ("ingest.commit", "ingest.stage")
    # the spans of one chunk share its identifier
    for c in chunks:
        mine = [s for s in learner if s.stats.get("chunk") == c.stats["chunk"]]
        assert [s.name for s in mine] == [
            "learner.chunk", "learner.dispatch", "learner.on_chunk"]


def test_a_block_keeps_its_id_from_stage_to_the_next_chunks_commit(
        recorder, loop_and_service, rng):
    loop, service, state = loop_and_service
    state, _m = loop.run(state, 2)  # compiles; nothing staged
    recorder.spans.clear()

    fed = []

    def feed(_state, _k):
        # rows arrive while chunk 1 runs: staged after chunk 2's dispatch,
        # committed before chunk 3's
        if not fed:
            fed.append(service.add(rows(rng, BLOCK, first=5000)))
            service.flush()

    state, _m = loop.run(state, 8, on_chunk=feed)
    by_name = lambda n: [s for s in recorder.spans if s.name == n]  # noqa: E731
    (stage,), (commit,) = by_name("fused.stage_block"), by_name(
        "fused.commit_staged")
    assert stage.parent == "ingest.stage" and commit.parent == "ingest.commit"
    assert stage.stats["block"] == commit.stats["block"]
    assert stage.stats["rows"] == commit.stats["rows"] == BLOCK
    assert stage.stats["wait_ms"] >= 0 and commit.stats["inflight_ms"] > 0
    (h2d,) = by_name("fused.h2d")
    assert h2d.parent == "fused.stage_block"
    # the commit is in the chunk after the one that staged the block
    order = [s for s in recorder.spans if s.name in (
        "learner.chunk", "fused.stage_block", "fused.commit_staged")]
    i, j = order.index(stage), order.index(commit)
    assert [s.name for s in order[i:j + 1]] == [
        "fused.stage_block", "learner.chunk", "fused.commit_staged"]
    # the ingest threads' side of the handoff
    (admit,) = by_name("ingest.admit")
    assert admit.stats["rows"] == BLOCK  # opened by the adding thread
    (host,) = by_name("ingest.host_stage")  # the commit thread's own
    seq = admit.stats["seq"]  # the ticket the add was given
    assert host.stats == {"rows": BLOCK, "batches": 1, "seq_lo": seq,
                          "seq_hi": seq, "through": stage.stats["through"],
                          "dropped": 0}
    assert host.parent is None
    # the block says the positions it carries, stage and commit alike
    assert stage.stats["through"] - stage.stats["first"] + 1 == BLOCK
    assert commit.stats["through"] == stage.stats["through"]


def _specs():
    return [((OBS,), np.float32), ((ACT,), np.float32), ((), np.float32),
            ((OBS,), np.float32), ((), np.float32), ((), np.float32)]


def test_overflowing_a_two_block_ring_counts_exactly_the_rows_lost(rng):
    from d4pg_tpu.obs import flight
    from d4pg_tpu.replay.fused_buffer import HostStagingRing

    ring = HostStagingRing(_specs(), BLOCK, 2)
    counter = REGISTRY.counter("fused.rows_dropped")
    before = counter.value
    ring.push(rows(rng, 2 * BLOCK))  # exactly full
    assert counter.value == before
    ring.push(rows(rng, 5, first=100))  # the five oldest go
    assert counter.value == before + 5 and len(ring) == 2 * BLOCK
    ring.pop(BLOCK)
    ring.push(rows(rng, 3 * BLOCK, first=200))  # more than a ring-full
    # BLOCK of the push itself never fits; of the BLOCK pending, all go
    assert counter.value == before + 5 + 2 * BLOCK
    assert len(ring) == 2 * BLOCK
    frame, n = ring.frame()
    assert n > 0 and frame.done[0] == 200 + BLOCK  # the newest ring-full
    events = [e for e in flight.RECORDER.events()
              if e["kind"] == "staging_drop"]
    assert [e["rows"] for e in events[-2:]] == [5, 2 * BLOCK]


def test_overflowing_a_multi_ring_counts_exactly_the_rows_lost(rng):
    from d4pg_tpu.replay.staging import MultiRingStaging

    staging = MultiRingStaging(_specs(), BLOCK, 2, shards=2)
    counter = REGISTRY.counter("fused.rows_dropped")
    before = counter.value
    staging.push(rows(rng, 2 * BLOCK), shard=0)
    staging.push(rows(rng, BLOCK), shard=1)
    assert counter.value == before
    staging.push(rows(rng, 7), shard=0)
    assert counter.value == before + 7
    # the merge into one frame stream moves rows on; it drops none
    total = 0
    while True:
        _frame, n = staging.frame()
        if n == 0:
            break
        assert staging.oldest_push() is not None
        staging.pop(n)
        total += n
    assert total == 3 * BLOCK and counter.value == before + 7


def test_oldest_push_is_the_time_of_the_oldest_pending_row(rng):
    from d4pg_tpu.replay.fused_buffer import HostStagingRing

    ring = HostStagingRing(_specs(), BLOCK, 2)
    assert ring.oldest_push() is None
    ring.push(rows(rng, 4), at=1.0)  # a time.monotonic() reading
    ring.push(rows(rng, 4), at=2.0)
    assert ring.oldest_push() == 1.0
    ring.pop(3)
    assert ring.oldest_push() == 1.0  # one row of the first push is left
    ring.pop(1)
    assert ring.oldest_push() == 2.0
    ring.pop(4)
    assert ring.oldest_push() is None and not ring._pushed


TOP = ("replay.sample", "replay.gather", "learner.update", "replay.writeback")
CHILDREN = ("update.critic", "update.actor", "update.optim")


def _while_bodies(text):
    """Names of the computations some ``while`` runs as its body."""
    import re

    return set(re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text))


def _computation(text, name):
    import re

    m = re.search(r"^(?:ENTRY )?%?" + re.escape(name) + r" [^\n]*\{\n(.*?)^\}",
                  text, re.S | re.M)
    assert m, name
    return m.group(1)


def test_compiled_text_holds_the_scopes(loop_and_service, rng):
    loop, service, state = loop_and_service
    assert service.add(rows(rng, BLOCK, first=7000))
    service.flush()
    state, _m = loop.run(state, 4)  # a flush (commit program) and 2 chunks
    text = trace.compiled_text("learner.chunk")
    bodies = [_computation(text, b) for b in _while_bodies(text)]
    assert bodies
    scan = max(bodies, key=len)  # the scan's body; a tree loop may nest
    for scope in TOP:
        assert scope in scan, scope
    for scope in CHILDREN:
        assert scope in text, scope
    commit = trace.compiled_text("ingest.commit")
    assert "ingest.ring_write" in commit and "ingest.tree_insert" in commit
    # the one compile that keys the cache with metadata leaves no trace
    import jax

    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    state, _m = loop.run(state, 2)  # and the program still dispatches


STALE = """
import sys, jax, jax.numpy as jnp
from d4pg_tpu.io.profiling import compiled_text_of
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) @ x
fn = jax.jit(f, donate_argnums=(0,))
fn(jnp.ones((32, 32))).block_until_ready()
arg = jax.ShapeDtypeStruct((32, 32), jnp.float32)
print(sys.argv[1] in fn.lower(arg).compile().as_text(),
      sys.argv[1] in compiled_text_of(fn, (arg,)))
"""


def test_compiled_text_is_not_the_compile_caches_stale_one(tmp_path):
    """The persistent cache keys a program with its metadata stripped: a
    second process whose only difference is a scope's name is handed the
    first one's executable, old names and all (PR 25's second chip call read
    0 under every scope). ``compiled_text_of`` must not be."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    out = [subprocess.run([sys.executable, "-c", STALE, scope], env=env,
                          capture_output=True, text=True, check=True,
                          timeout=120).stdout.split()
           for scope in ("first.scope", "second.scope")]
    assert out[0] == ["True", "True"]
    assert out[1][1] == "True"
    # the hazard itself, so that this test says when jax stops having it
    assert out[1][0] == "False"


def test_obs_imports_no_jax_and_spans_are_null_without_an_annotator():
    # (since PR 52: null once the start-up log is full; until then a span
    # with no annotator is kept there, tests/test_startup_log.py)
    code = ("import sys; import d4pg_tpu.obs; from d4pg_tpu.obs import trace;"
            "assert 'jax' not in sys.modules, 'obs imported jax';"
            "from d4pg_tpu.obs.startup_log import LOG; LOG.full = True;"
            "s = trace.span('x', a=1); assert s is trace.NULL_SPAN;"
            "\nwith s as t: t.set_metadata(b=2)")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)
    with trace.span("learner.run", n=1) as sp:
        sp.set_metadata(rows=0)
