"""What the learner drivers share: build the cell, drive its first chunk for
the output check, stamp chunk completions without draining the device, run
the measured (or traced) window, and compare with the plain reference.

Observing completions. ``FusedLoop.run(..., on_chunk=...)`` calls the hook
after chunk *t* is dispatched. The hook enqueues a one-scalar reduction of
the new state (it runs when chunk *t* ends) and then blocks on the scalar
of chunk *t-depth*, stamping the clock. ``depth`` chunks are always queued
behind the running one, so the measurement never starves the device. The
scalars are kept: a non-finite one is a failed chunk.

``depth`` is 1 unless the traffic file gives ``queue_ahead_s``: then it is
as many chunks as fill that time, by the chunk time warm-up measured, and at
most ``MAX_QUEUED_CHUNKS``. The TPU runtime holds 32 programs in flight
and a chunk is two (itself and its scalar); past that the dispatch blocks,
not the wait, and a stamp is no longer taken as its own chunk ends (PR 24:
asked for 21, the first stamp of the window came 298 ms late). The shipped loop dispatches a whole cycle
without blocking (``train.train_steps_fused``), so a deep queue is how the
program runs; and while the host process is stalled (the one-chip machine
shares its cores) the device works on through the queue, so a stall shorter
than ``queue_ahead_s`` costs the window nothing. The stamps stay completion
times as long as the host is ahead of the device; after a stall the
overdue ones come at once, one long interval and some short ones.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time

import numpy as np

from benchmark import cellbuild, datagen, reference

P95_MIN_SAMPLES = 20
MAX_QUEUED_CHUNKS = 14  # 2 programs a chunk, under the runtime's 32 in flight


class CheckFailed(RuntimeError):
    """A run that must not print a result line; the message says why."""


@dataclasses.dataclass
class RunEnv:
    cell: dict
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    fault: str
    t_start: float  # perf_counter at process start
    trace_dir: str
    wanted: frozenset  # names of the metrics this cell reports in this mode
    compile_seconds: object  # callable(): compile seconds so far
    log: object  # callable(str): a line on stderr

    @property
    def seed32(self) -> int:
        return datagen.fold_seed(self.seed)


def percentile(samples, q: float, what: str) -> float:
    """``q``-th percentile (nearest rank above); a sample too small to
    have a tail is refused, not summarised."""
    if len(samples) < P95_MIN_SAMPLES:
        raise CheckFailed(f"{what}: {len(samples)} samples, under the "
                          f"{P95_MIN_SAMPLES} a percentile needs")
    return float(np.percentile(np.asarray(samples, np.float64), q,
                               method="higher"))


class ChunkClock:
    """Completion stamps of consecutive chunks (see module docstring)."""

    def __init__(self, annotate):
        import jax
        import jax.numpy as jnp

        self._touch = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32)))
        self._annotate = annotate
        self.depth = 1  # chunks left queued behind the one waited for
        self.dispatched: list[float] = []  # hook entry time per chunk
        self.done: list[float] = []  # completion stamp per chunk
        self.marks: list = []  # device scalars, one per chunk; those past
        # len(done) have not been waited for
        self.hooks: list = []  # extra per-chunk callbacks(chunk_index, now)

    def on_chunk(self, state, _k) -> None:
        import jax

        now = time.perf_counter()
        index = len(self.dispatched)
        self.dispatched.append(now)
        for hook in self.hooks:
            hook(index, now)
        leaf = jax.tree_util.tree_leaves(state.critic_params)[0]
        self.marks.append(self._touch(leaf))
        while len(self.marks) - len(self.done) > self.depth:
            with self._annotate("bench.wait_prev"):
                self.wait_next()

    def finish(self) -> float:
        """Block on every chunk still queued; returns the last one's
        stamp."""
        while len(self.done) < len(self.marks):
            self.wait_next()
        return self.done[-1]

    def wait_next(self) -> None:
        """Block on the oldest chunk not yet waited for and stamp it."""
        self.marks[len(self.done)].block_until_ready()
        self.done.append(time.perf_counter())

    def chunk_s(self) -> float:
        """A chunk's length: the median of the last intervals (after a host
        stall one reads long and up to ``depth`` read near zero)."""
        return float(np.median(np.diff(self.done[-64:])))

    def nonfinite(self, first: int = 0) -> int:
        import jax.numpy as jnp

        if len(self.marks) <= first:
            return 0
        vals = np.asarray(jnp.stack(self.marks[first:]))
        return int(np.sum(~np.isfinite(vals)))


class HostPulse:
    """A thread that only sleeps and wakes: the longest it overslept tells
    a stalled host (every thread late) from a slow device (this one on
    time) when a chunk interval reads long."""

    PERIOD = 0.01

    def __init__(self):
        self._stop = threading.Event()
        self._worst = 0.0
        self._thread = threading.Thread(target=self._run, name="bench-pulse",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        t = time.perf_counter()
        while not self._stop.wait(self.PERIOD):
            now = time.perf_counter()
            self._worst = max(self._worst, now - t - self.PERIOD)
            t = now

    def stop(self) -> float:
        self._stop.set()
        self._thread.join()
        return self._worst


class LearnerCell:
    """One learner cell through set-up, window and check. ``service_for``
    builds the ``ReplayService`` round the buffer for the ingest driver."""

    def __init__(self, env: RunEnv, service_for=None):
        import jax
        from d4pg_tpu.io.profiling import RecompileSentinel

        self.env = env
        self.annotate = jax.profiler.TraceAnnotation
        cfg = env.cfg
        self.config = cellbuild.learner_config(cfg)
        self.k = int(cfg["learner"]["k"])
        env.log(f"[setup] {time.perf_counter() - env.t_start:7.2f} s  "
                "backend up, files read")
        self.state = cellbuild.build_state(self.config, env.seed32)
        self._stage("state built")
        self.buffer = cellbuild.build_buffer(cfg, self.config, env.seed32)
        self._stage("ring filled")
        self.service = service_for(self.buffer) if service_for else None
        self.loop = cellbuild.build_loop(cfg, self.config, self.buffer,
                                         self.service)
        if env.fault == "frozen_step":
            self._freeze_step()
        self.clock = ChunkClock(self.annotate)
        self.sentinel = RecompileSentinel()
        self.spans = {"commit": [], "stage": []}
        if self.loop.ingest is not None:
            self._span_ingest()
        self.first = None

    def _stage(self, what: str) -> None:
        """Where set-up's seconds go, on stderr."""
        import jax

        jax.block_until_ready(self.state)
        self.env.log(f"[setup] {time.perf_counter() - self.env.t_start:7.2f}"
                     f" s  {what}")

    # -- faults the tests inject (rehearsal only) ---------------------------
    def _freeze_step(self) -> None:
        """Break the timed path: the chunk computes but hands back (a copy
        of) the state it was given."""
        import jax
        import jax.numpy as jnp

        fn = self.loop.fused_for(self.k)

        def frozen(state, trees, storage, size):
            kept = jax.tree_util.tree_map(jnp.copy, state)  # fn donates
            _new, trees, metrics = fn(state, trees, storage, size)
            return kept, trees, metrics

        self.loop._fns[self.k] = frozen

    def _span_ingest(self) -> None:
        """Host spans round the loop's two ingest calls."""
        ingest = self.loop.ingest
        for name in ("commit", "stage"):
            inner = getattr(ingest, name)

            def spanned(inner=inner, name=name):
                t = time.perf_counter()
                with self.annotate("bench." + name):
                    n = inner()
                self.spans[name].append(time.perf_counter() - t)
                return n

            setattr(ingest, name, spanned)

    # -- set-up -------------------------------------------------------------
    def first_chunk(self) -> None:
        """Drive the loop object the window will use through its first
        chunk, by the window's own call, and keep what the check compares:
        the chunk's per-step metrics and copies of the state's Adam first
        moments, parameters and the sum tree."""
        import jax
        import jax.numpy as jnp

        self.state, m = self.loop.run(self.state, self.k)
        st = self.state
        copy = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))
        self.first = {
            "device": copy({
                "critic_mu": st.critic_opt_state[0].mu,
                "actor_mu": st.actor_opt_state[0].mu,
                "critic": st.critic_params, "actor": st.actor_params,
                "sum_tree": self.buffer.trees.sum_tree}),
            "metrics": {k: np.asarray(m[k]) for k in (
                "critic_loss", "actor_loss", "td_error", "idx")},
            "size": int(self.buffer.size),
        }
        self._stage("first chunk run and copied")
        if self.env.fault == "nan_loss":
            self.state = self.state._replace(
                critic_params=jax.tree_util.tree_map(
                    lambda x: x * jnp.nan, self.state.critic_params))

    def warm(self) -> None:
        """Two more chunks by the window's call with the clock attached, so
        the mark program and (ingest) the leading flush's stage and commit
        are compiled before the window."""
        self.state, _m = self.loop.run(self.state, 2 * self.k,
                                       on_chunk=self.clock.on_chunk)
        self.clock.finish()
        self._stage("warm")

    # -- the window ---------------------------------------------------------
    def run_window(self, on_open=None, on_close=None) -> dict:
        """Measured window: calls of ``chunks_per_call`` chunks until
        ``seconds`` have passed, closed by blocking on the last chunk.
        Traced: ``trace_calls`` calls inside one ``bench.window``
        annotation with the profiler on. ``on_open``/``on_close`` run just
        inside the window's ends (the ingest driver starts and stops its
        actors there, so none runs while the profiler starts or stops).
        Returns the window's own numbers."""
        import jax

        env, clock = self.env, self.clock
        per_call = int(env.traffic["chunks_per_call"])
        max_calls = int(env.traffic["trace_calls"]) if env.trace else None
        first = len(clock.done)
        last = [None]
        ahead = float(env.traffic.get("queue_ahead_s", 0.0))
        clock.depth = max(1, min(MAX_QUEUED_CHUNKS,
                                 math.ceil(ahead / clock.chunk_s())))
        env.log(f"[window] {clock.depth} chunk(s) kept queued "
                f"(queue_ahead_s {ahead:g}, chunk {clock.chunk_s():.4f} s)")

        def calls(t0):
            """Calls of up to ``chunks_per_call`` chunks until ``seconds``
            have passed (traced: or ``trace_calls`` calls are made). A call
            never asks for more chunks than the window has room for beside
            those still queued, judged by the recent chunks' length, so the
            window ends within a chunk after ``seconds`` whatever a chunk
            takes (2.4 s in the pixel cell)."""
            made = 0
            while max_calls is None or made < max_calls:
                left = env.seconds - (time.perf_counter() - t0)
                if made and left <= 0:
                    break
                queued = len(clock.dispatched) - len(clock.done)
                room = math.ceil(left / clock.chunk_s()) - queued
                if queued and room < 1:  # the queue reaches the window's end
                    clock.wait_next()
                    continue
                with self.annotate("bench.dispatch"):
                    self.state, last[0] = self.loop.run(
                        self.state, max(1, min(per_call, room)) * self.k,
                        on_chunk=clock.on_chunk)
                made += 1

        # Set-up leaves millions of long-lived objects (JAX's traced and
        # compiled programs); a full collection that walks them holds every
        # Python thread. Moving them out of the collector's view keeps the
        # window's collections short (0.5 ms at most in PR 24's runs); what
        # they cost is printed, beside the longest chunk interval, so a
        # stall can be told from a pause.
        gc.collect()
        gc.freeze()
        pauses, began = [], [0.0]

        def on_gc(phase, _info):
            if phase == "start":
                began[0] = time.perf_counter()
            else:
                pauses.append(time.perf_counter() - began[0])

        gc.callbacks.append(on_gc)
        pulse = HostPulse()
        with self.sentinel:
            self.compile_s = env.compile_seconds()  # all of it is set-up
            t0 = time.perf_counter()
            if env.trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(env.trace_dir,
                                         profiler_options=opts)
                try:
                    t0 = time.perf_counter()
                    with self.annotate("bench.window"):
                        if on_open:
                            on_open()
                        calls(t0)
                        t1 = clock.finish()
                        if on_close:
                            on_close()
                finally:
                    jax.profiler.stop_trace()
            else:
                if on_open:
                    on_open()
                calls(t0)
                t1 = clock.finish()
                if on_close:
                    on_close()
        last_metrics = last[0]
        gc.callbacks.remove(on_gc)
        host_gap = pulse.stop()
        chunks = len(clock.done) - first
        stamps = np.asarray([t0] + clock.done[first:])
        gaps = np.diff(stamps)
        env.log(f"[window] {len(pauses)} collections, longest "
                f"{max(pauses, default=0.0) * 1e3:.2f} ms; longest chunk "
                f"interval {np.max(gaps) * 1e3:.2f} ms "
                f"{stamps[np.argmax(gaps)] - t0:.2f} s into the window, "
                f"median {np.median(gaps) * 1e3:.2f} ms; a host thread "
                f"waking every {HostPulse.PERIOD * 1e3:.0f} ms was held up "
                f"{host_gap * 1e3:.2f} ms at most")
        if env.fault == "no_samples":
            stamps = stamps[:2]
        bad = clock.nonfinite(first)
        if last_metrics is not None and not np.all(np.isfinite(
                np.asarray(last_metrics["critic_loss"]))):
            bad = max(bad, 1)
        return {
            "t0": t0, "t1": t1, "chunks": chunks, "steps": chunks * self.k,
            "window_s": t1 - t0, "intervals_s": np.diff(stamps),
            "nonfinite_chunks": bad,
            "compiles_in_window": int(self.sentinel.compilations),
        }

    def memory_peak_bytes(self) -> int:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            return int(stats["peak_bytes_in_use"])
        if not self.env.rehearsal:
            raise CheckFailed("the device reports no peak_bytes_in_use")
        import resource  # the CPU backend keeps no device memory stats

        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024

    def release(self) -> None:
        """Free the program's device state before the reference runs."""
        if self.loop is not None:
            self.loop.close()
        self.loop = self.buffer = self.state = self.service = None
        self.clock.marks.clear()
        gc.collect()

    # -- the check ----------------------------------------------------------
    def follow_reference(self, ops=None) -> dict:
        """The plain reference over the first chunk's rows, from the seed:
        per-step losses and TD errors, Adam first moments and parameters
        after the chunk's K steps, and the seeded parameters it started
        from. ``ops`` chooses the precision (``reference.LOWP_OPS`` is the
        control). Call after ``release()``."""
        import jax
        import jax.numpy as jnp

        env, cfg, config = self.env, self.env.cfg, self.config
        lr = cfg["learner"]
        idx_all = self.first["metrics"]["idx"]
        s = jnp.uint32(env.seed32)
        spec = cellbuild.row_spec(cfg, config)
        actor0, critic0 = jax.jit(
            lambda s: cellbuild.seeded_params(config, s))(s)
        mirror = reference.PriorityMirror(
            np.asarray(cellbuild.seeded_p_alpha(cfg, env.seed32)),
            lr["per_alpha"], lr["per_beta0"], int(lr["per_beta_steps"]))
        make_rows = jax.jit(lambda s, idx: datagen.rows(jnp, s, idx, spec))

        def feed(t):
            obs, action, reward, nxt, _done, discount = make_rows(
                s, jnp.asarray(idx_all[t]))
            return idx_all[t], (obs, action, reward, nxt, discount)

        ref, st = reference.follow(
            cfg["model"], ops or reference.EXACT_OPS, actor0, critic0,
            jax.random.key(s), feed, mirror, self.k)
        ref.update(critic_mu=st["cm"], actor_mu=st["am"],
                   critic=st["critic"], actor=st["actor"],
                   critic0=critic0, actor0=actor0)
        return ref

    def compare(self, prog: dict, ref: dict) -> dict:
        """The numbers compared, ``prog`` against ``ref``: worst per-step
        relative gap of each loss, the first step's TD-error vector, and by
        the worst leaf the Adam first moments (the gradients as the
        optimiser got them, the last steps weighing most) and the
        parameters' change over the chunk. A leaf's gap is the difference
        of the two norms over the reference's norm of that leaf or of the
        median leaf, whichever is larger."""
        import jax

        def rel(p, r, floor=0.0):
            return float(np.max(np.abs(p - r) / np.maximum(np.abs(r), floor)))

        def worst_leaf(p_tree, r_tree):
            p, r = reference.leaf_norms(p_tree), reference.leaf_norms(r_tree)
            floor = max(float(np.median(r)), 1e-30)  # some leaves are 0
            return float(np.max(np.abs(p - r) / np.maximum(r, floor)))

        sub = lambda a, b: jax.tree_util.tree_map(  # noqa: E731
            lambda x, y: x - y, a, b)
        model = self.env.cfg["model"]
        span = float(model["v_max"] - model["v_min"])
        return {
            "critic_loss_gap": rel(prog["critic_loss"], ref["critic_loss"]),
            "actor_loss_gap": rel(prog["actor_loss"], ref["actor_loss"],
                                  1e-3 * span),
            "td_gap": float(
                np.linalg.norm(prog["td_error"][0] - ref["td_error"][0])
                / np.linalg.norm(ref["td_error"][0])),
            "moment_gap": max(
                worst_leaf(prog["critic_mu"], ref["critic_mu"]),
                worst_leaf(prog["actor_mu"], ref["actor_mu"])),
            "update_gap": max(
                worst_leaf(sub(prog["critic"], ref["critic0"]),
                           sub(ref["critic"], ref["critic0"])),
                worst_leaf(sub(prog["actor"], ref["actor0"]),
                           sub(ref["actor"], ref["actor0"]))),
        }

    def check_first_chunk(self) -> dict:
        """The program's first chunk against the plain reference, plus the
        replay protocol's own invariants. Call after ``release()``."""
        first = self.first
        prog = dict(first["metrics"])
        prog.update({k: first["device"][k] for k in (
            "critic_mu", "actor_mu", "critic", "actor")})
        out = self.compare(prog, self.follow_reference())
        out.update(self._check_replay(
            np.asarray(first["device"]["sum_tree"]), prog["idx"],
            prog["td_error"], first["size"],
            self.env.cfg["learner"]["per_alpha"]))
        return out

    def control_numbers(self) -> dict:
        """The control: the reference in the next lower precision (fp8
        matmul inputs) put in the program's place. Not run by the
        benchmark's own runs; ``benchmark/tools/calibrate.py`` and the
        tests read it."""
        return self.compare(self.follow_reference(reference.LOWP_OPS),
                            self.follow_reference())

    @staticmethod
    def _check_replay(sum_tree, idx, td, size, alpha) -> dict:
        """The replay protocol after the first chunk: sampled indices are
        live rows, each last-written leaf holds ``(|td| + eps) ** alpha``,
        the root is the sum of the leaves."""
        cap = sum_tree.shape[0] // 2
        leaves = sum_tree[cap:].astype(np.float64)
        expect = {}
        for t in range(idx.shape[0]):
            row_idx, row_td = idx[t], td[t]
            uniq, counts = np.unique(row_idx, return_counts=True)
            dup = set(uniq[counts > 1].tolist())
            for i, v in zip(row_idx.tolist(), row_td.tolist()):
                # a slot drawn twice in one step keeps either write
                expect[i] = None if i in dup else v
        gaps = [abs(leaves[i] - (abs(v) + reference.PRIORITY_EPS) ** alpha)
                / ((abs(v) + reference.PRIORITY_EPS) ** alpha)
                for i, v in expect.items() if v is not None]
        return {
            "idx_out_of_range": int(np.sum((idx < 0) | (idx >= size))),
            "leaf_gap": float(max(gaps)) if gaps else 0.0,
            "root_gap": float(abs(float(sum_tree[1]) - leaves.sum())
                              / leaves.sum()),
        }


def judge(numbers: dict, limits: dict, log) -> bool:
    """Print each number compared beside its limit; true when every one is
    within it. A number with no limit in the configuration is an error: the
    check may not grow silently lax; ``null`` marks one the configuration
    has shown to hold no limit (its ``limits_why`` says so)."""
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise CheckFailed(f"no limit for {name} in the configuration")
        if limits[name] is None:  # the configuration says why
            log(f"[check] {name} = {value:.6g}  not compared")
            continue
        good = bool(np.isfinite(value)) and value <= limits[name]
        ok &= good
        log(f"[check] {name} = {value:.6g}  limit {limits[name]:.6g}  "
            f"{'ok' if good else 'EXCEEDED'}")
    return ok


def report(cell: LearnerCell, window: dict, *, attempted: int, failed: int,
           end_to_end=None, numbers=None, layer_ctx=None) -> dict:
    """What every learner driver hands back to ``run.py``: read the memory
    peak, free the program, run the check, and put the window's numbers
    under the metric names of ``BENCHMARK.json``."""
    env = cell.env
    peak_bytes = cell.memory_peak_bytes()
    k, config = cell.k, cell.config
    if window["nonfinite_chunks"]:
        raise CheckFailed(f"{window['nonfinite_chunks']} chunk(s) of the "
                          "window have a non-finite loss")
    e2e = {"setup_s": window["t0"] - env.t_start}
    if not env.trace:
        e2e["grad_steps_per_s"] = window["steps"] / window["window_s"]
        if "chunk_ms.p95" in env.wanted:
            e2e["chunk_ms.p95"] = percentile(
                window["intervals_s"], 95, "chunk_ms.p95") * 1e3
    e2e.update(end_to_end or {})
    ctx = {"spans": cell.spans, "k": k, "compile_s": cell.compile_s,
           "chunk_program": env.traffic.get("chunk_program", "jit_fn"),
           "log": env.log}
    ctx.update(layer_ctx or {})
    cell.release()
    t = time.perf_counter()
    checked = cell.check_first_chunk()
    checked["compiles_in_window"] = window["compiles_in_window"]
    checked.update(numbers or {})
    correct = judge(checked, env.cfg["limits"], env.log)
    env.log(f"[check] reference and comparison took "
            f"{time.perf_counter() - t:.2f} s; window "
            f"{window['window_s']:.3f} s, {window['chunks']} chunks of "
            f"{k} steps ({config.compute_dtype})")
    return {"attempted": int(attempted), "failed": int(failed),
            "correct": correct, "end_to_end": e2e, "layer_ctx": ctx,
            "memory_peak_bytes": peak_bytes}
