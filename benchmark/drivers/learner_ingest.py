"""The learner while actors stream: ``ReplayService`` and ``IngestOverlap``
live, ``actors`` in-process threads each handing ``ReplayService.add`` a
batch of ``rows_per_add`` seeded rows ``adds_per_s`` times a second on an
open schedule. Every add is timed from when it was *due*; how late the
generator ran is printed. TCP, codecs and the sharded receiver are not here.

Following a batch to its first gradient. One shard, one staging ring: rows
reach the device in the order ``FusedDeviceReplay.add`` received them. The
driver wraps that method to note, per batch, the running row count at which
it was staged; a chunk hook compares it with ``IngestOverlap.rows_committed``
to find the first chunk dispatched after the batch's commit, and the chunk
clock gives that chunk's completion on the device.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import cellbuild, datagen
from benchmark.learner import (CheckFailed, LearnerCell, RunEnv, percentile,
                               report)

POOL = 8  # distinct seeded batches an actor cycles through


class Actors:
    """The in-process actor fleet: threads, schedule, per-add records."""

    def __init__(self, env: RunEnv, service, spec: dict, annotate):
        t = env.traffic
        self.n = int(t["actors"])
        self.rows = int(t["rows_per_add"])
        self.rate = float(t["adds_per_s"])
        self.service = service
        self.annotate = annotate
        self.stop = threading.Event()
        self.t_start = None
        # the same arrivals for every seed, in another order: the seed
        # permutes which actor gets which phase of the period
        rng = np.random.default_rng(env.seed32)
        self.phase = rng.permutation(self.n) / float(self.n)
        seed32 = np.uint32(env.seed32)
        self.pool = [[
            list(datagen.rows(np, seed32,
                              self._pool_rows(a, p), spec))
            for p in range(POOL)] for a in range(self.n)]
        self.records = [[] for _ in range(self.n)]  # (j, due, sent, ok)
        self.threads = [threading.Thread(
            target=self._run, args=(a,), name=f"bench-actor-{a}", daemon=True)
            for a in range(self.n)]

    def _pool_rows(self, a: int, p: int) -> np.ndarray:
        # generator rows far above any ring index: one block per pool entry
        return (1 << 30) + ((a * POOL + p) * self.rows
                            + np.arange(self.rows)).astype(np.int64)

    def seq(self, a: int, j: int) -> int:
        """First sequence number of actor ``a``'s ``j``-th batch; a row's
        number is this plus its position. Carried in ``done``, which the
        update never reads; exact in float32 below 2**24."""
        return (j * self.n + a) * self.rows

    def batch(self, a: int, j: int):
        from d4pg_tpu.replay.uniform import TransitionBatch

        obs, action, reward, nxt, _done, discount = self.pool[a][j % POOL]
        done = (self.seq(a, j) + np.arange(self.rows)).astype(np.float32)
        return TransitionBatch(obs, action, reward, nxt, done, discount)

    def expected_row(self, seq: int):
        """The row the generator made for sequence number ``seq``."""
        slot, r = divmod(int(seq), self.rows)
        j, a = divmod(slot, self.n)
        return [np.asarray(f[r]) for f in self.batch(a, j)]

    def _run(self, a: int) -> None:
        period = 1.0 / self.rate
        j = 0
        while not self.stop.is_set():
            due = self.t_start + (j + self.phase[a]) * period
            delay = due - time.perf_counter()
            if delay > 0 and self.stop.wait(delay):
                break
            sent = time.perf_counter()
            with self.annotate("bench.sender"):
                ok = self.service.add(self.batch(a, j), actor_id=f"actor{a}",
                                      block=True, timeout=5.0)
            self.records[a].append((j, due, sent, bool(ok)))
            j += 1

    def start(self) -> None:
        self.t_start = time.perf_counter() + 0.05
        for t in self.threads:
            t.start()

    def join(self) -> None:
        self.stop.set()
        for t in self.threads:
            t.join(timeout=10.0)
        alive = [t.name for t in self.threads if t.is_alive()]
        if alive:
            raise CheckFailed(f"actor threads did not stop: {alive}")


def run(env: RunEnv) -> dict:
    from d4pg_tpu.distributed.replay_service import ReplayService

    cell = LearnerCell(env, service_for=lambda buf: ReplayService(
        buf, num_ingest_shards=1))
    service, buffer, clock = cell.service, cell.buffer, cell.clock
    spec = cellbuild.row_spec(env.cfg, cell.config)
    actors = Actors(env, service, spec, cell.annotate)

    # (first sequence number, running rows staged) per batch, in the order
    # the commit thread staged them (it holds the buffer lock here)
    staged: list[tuple[int, int]] = []
    inner_add = buffer.add

    def noting_add(batch):
        out = inner_add(batch)
        total = (staged[-1][1] if staged else 0) + int(batch.obs.shape[0])
        staged.append((int(batch.done[0]), total))
        return out

    buffer.add = noting_add

    cell.first_chunk()
    # warm the ingest programs (device_put of a block frame, the fused ring
    # write + tree insert) with one batch from a spare actor slot, after
    # the checked chunk so that chunk sampled only the seeded fill
    warm_j = 8000  # beyond any window's batch count, below 2**24 rows
    if not service.add(actors.batch(0, warm_j), actor_id="warm"):
        raise CheckFailed("the warm-up batch was refused")
    service.flush()
    cell.warm()
    if cell.loop.ingest.rows_committed != staged[-1][1]:
        raise CheckFailed("the warm-up batch was not committed")

    # per batch: the chunk index first dispatched after its commit, and the
    # hook time that saw it
    seen: dict[int, tuple[int, float]] = {}
    cursor = [len(staged)]

    def on_chunk(index: int, now: float) -> None:
        committed = cell.loop.ingest.rows_committed
        i = cursor[0]
        while i < len(staged) and staged[i][1] <= committed:
            seen[staged[i][0]] = (index, now)
            i += 1
        cursor[0] = i

    clock.hooks.append(on_chunk)
    try:
        window = cell.run_window(on_open=actors.start, on_close=actors.join)
    finally:
        actors.join()
    service.flush(timeout=10.0)
    # one more short call: its leading flush commits what is still staged,
    # and its chunks give the window's last batches their completion
    cell.state, _m = cell.loop.run(cell.state, 2 * cell.k,
                                   on_chunk=clock.on_chunk)
    clock.finish()
    stats = service.ingest_stats()

    records = [(a, *r) for a in range(actors.n) for r in actors.records[a]]
    offered = len(records) * actors.rows
    refused = sum(1 for r in records if not r[4]) * actors.rows
    late = np.asarray([r[3] - r[2] for r in records] or [0.0])
    env.log(f"[ingest] {len(records)} adds offered "
            f"({offered / max(window['window_s'], 1e-9):.0f} rows/s), "
            f"generator lateness median {np.median(late) * 1e3:.3f} ms "
            f"p95 {np.percentile(late, 95) * 1e3:.3f} ms "
            f"max {late.max() * 1e3:.3f} ms; sheds {stats['sheds']} "
            f"admit_fails {stats['admit_fails']}; rows refused {refused}")
    row_to_grad, admit_to_commit, lost = [], [], 0
    for a, j, due, _sent, ok in records:
        if not ok:
            continue
        hit = seen.get(actors.seq(a, j))
        if hit is None or hit[0] >= len(clock.done):
            lost += 1
            continue
        admit_to_commit.append(hit[1] - due)
        row_to_grad.append(clock.done[hit[0]] - due)

    mismatched = _read_back(cell, actors, env)
    # shed or fenced rows never reach the ring, so they are among the lost
    failed = refused + lost * actors.rows + mismatched
    # a row the staging ring dropped (its documented answer to a backlog
    # deeper than itself) is a failed operation, not a wrong answer; what
    # reached the ring must be what was sent, in admission order
    numbers = {
        "rows_mismatched": mismatched,
        "order_breaks": int(stats["order_breaks"]),
    }
    e2e = {}
    if not env.trace:
        e2e["row_to_grad_ms.p95"] = percentile(
            row_to_grad, 95, "row_to_grad_ms.p95") * 1e3
    service.close()
    return report(cell, window, attempted=offered, failed=failed,
                  end_to_end=e2e, numbers=numbers,
                  layer_ctx={"admit_to_commit_s": admit_to_commit})


def _read_back(cell: LearnerCell, actors: Actors, env: RunEnv) -> int:
    """Rows among the newest committed that differ from what the generator
    made for the sequence number they carry."""
    import jax

    buffer = cell.buffer
    n = min(int(env.traffic["readback_rows"]), buffer.capacity)
    slots = (buffer.head - 1 - np.arange(n)) % buffer.capacity
    got = jax.device_get([arr[slots] for arr in buffer.storage])
    bad = 0
    for r in range(n):
        want = actors.expected_row(got[4][r])
        if not all(np.array_equal(np.asarray(g[r]), w)
                   for g, w in zip(got, want)):
            bad += 1
    return bad
