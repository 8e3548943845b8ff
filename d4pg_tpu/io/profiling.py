"""Profiling: XLA trace capture, step-rate tracking, perf sentinels.

SURVEY.md §5: the reference's only timing is wall-clock deltas into a dict
that is never persisted (``main.py:250, 359``). Here: ``jax.profiler``
traces on demand (viewable in TensorBoard/Perfetto) and an EWMA'd
grad-steps/sec meter — the north-star metric (BASELINE.md) — cheap enough
to leave on.

The sentinels are the runtime complement of the static ``jaxlint``
pass (``d4pg_tpu/lint``): the linter catches hazards it can see in the
AST; the sentinels catch what it can't — a hot loop that recompiles in
steady state (``RecompileSentinel``, wired into ``train.py`` and the
learner tests), round-trips data between host and device per step
(``TransferSentinel``), or compiles to a program that silently reshards
a tree between layouts (``ReshardSentinel``, the dynamic twin of the
``sharding-spec-drift`` lint family the way RecompileSentinel twins
``recompile-hazard``).
"""

from __future__ import annotations

import contextlib
import threading
import time


@contextlib.contextmanager
def xla_trace(log_dir: str | None):
    """Capture an XLA profiler trace into ``log_dir`` (no-op when None)."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        yield


def abstract_args(args: tuple) -> tuple:
    """``args`` with every array replaced by its ``jax.ShapeDtypeStruct``
    (shape and dtype): what a program-table entry keeps
    (``obs/trace.register_program``), so a trace reader can lower and
    compile the program again while nothing on the device stays alive.
    An array committed to one device carries its ``format`` (device and
    layout), as the call resolved it: the fused buffer pins its ring's
    layout that way (``replay/device_ring.py``), and a program lowered
    for the default layout is another program, with other instructions.
    Nothing else carries a sharding: an uncommitted argument that names
    one lowers to another module than the call made (a compile-cache
    miss of seconds, my chip run, PR 25), and the mesh programs state
    theirs in ``jax.jit``. Python scalars pass through as they are."""
    import jax
    from jax.sharding import SingleDeviceSharding

    def leaf(x):
        if isinstance(x, jax.Array):
            pinned = x.committed and isinstance(x.sharding,
                                                SingleDeviceSharding)
            # (a typed key array has a sharding and no format)
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(
                    x, "format", x.sharding) if pinned else None)
        return x

    return jax.tree_util.tree_map(leaf, args)


def compiled_text_of(fn, args: tuple) -> str:
    """The compiled HLO text of jitted ``fn`` for (abstract) ``args``, with
    the ``op_name`` metadata of the source AS IT IS NOW. The persistent
    compile cache keys a program with its metadata stripped, so a plain
    ``fn.lower(*args).compile()`` can hand back an executable compiled
    before a ``named_scope`` was written, under its old names (my chip
    run, PR 25: every scope read 0). Here the key includes the metadata
    for this one compile, and the function's in-memory executable is
    dropped first (the next real call reloads it from the cache): the
    first call after a source change compiles, later ones hit."""
    import jax

    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        fn.clear_cache()
        return fn.lower(*args).compile().as_text()
    finally:
        jax.config.update(key, before)


@contextlib.contextmanager
def fresh_compile():
    """Compile what runs inside without the persistent compile cache,
    neither read nor written. For a program that RETURNS an array in a
    pinned (non-default) layout: an executable read back from the cache
    (jax 0.9.0, libtpu 0.0.34) hands out arrays that report the device's
    default layout whatever layout they have, and ``jax.jit`` lays out
    the next program's parameters by that report, so the next program
    refuses the array ("expected parameter 0 of size ... but got buffer
    with incompatible size"; my chip run, PR 31). A freshly compiled
    executable tells the truth, call after call. The cache's state is
    process-wide: a compile another thread starts meanwhile is not
    cached either, which costs that thread a compile and nothing else."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    flag = "jax_enable_compilation_cache"
    before = getattr(jax.config, flag)
    jax.config.update(flag, False)
    compilation_cache.reset_cache()  # the decision is taken once a process
    try:
        yield
    finally:
        jax.config.update(flag, before)
        compilation_cache.reset_cache()


class FreshProgram:
    """A jitted ``fn`` compiled ahead of its first call under
    ``fresh_compile``, once per argument signature, and called through
    the compiled object from then on: nothing that clears or refills the
    function's own caches (``compiled_text_of``) can put an executable
    from the persistent cache behind it. ``fn`` stays reachable for the
    program table."""

    def __init__(self, fn):
        self.fn = fn
        self._compiled: dict = {}

    def __call__(self, *args):
        import jax

        key = tuple((getattr(a, "shape", ()), getattr(a, "dtype", type(a)))
                    for a in jax.tree_util.tree_leaves(args))
        compiled = self._compiled.get(key)
        if compiled is None:
            with fresh_compile():
                compiled = self.fn.lower(*args).compile()
            self._compiled[key] = compiled
        return compiled(*args)


class StepTimer:
    """EWMA steps/sec over explicitly bracketed update spans.

    ``start()`` ... ``stop(n)`` measures ONLY the bracketed region, so the
    reported rate is pure update throughput — not diluted by eval/collect/
    checkpoint time happening between brackets.
    """

    def __init__(self, alpha: float = 0.9):
        self._alpha = alpha
        self._t0: float | None = None
        self.rate: float | None = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, n_steps: int) -> float | None:
        if self._t0 is None:
            return self.rate
        dt = time.perf_counter() - self._t0
        self._t0 = None
        if dt > 0 and n_steps > 0:
            inst = n_steps / dt
            self.rate = (
                inst if self.rate is None
                else self._alpha * self.rate + (1 - self._alpha) * inst
            )
        return self.rate


class RecompileError(AssertionError):
    """A region that must be compile-free triggered XLA compilation."""


class RecompileSentinel:
    """Counts XLA backend compilations inside the bracketed region.

    Zero steady-state recompilation is a core throughput invariant of this
    stack (every surprise compile stalls the learner for seconds): after
    warmup, wrap the hot loop and call :meth:`assert_clean`.

    Detection uses ``jax.monitoring``'s event stream — every backend
    compile (or load from the persistent compile cache) records a
    ``/jax/core/compile/backend_compile_duration`` event, and hits in
    the in-memory jit cache record nothing — so ANY jitted callable
    (including scans/shard_maps nested in it) is observed without
    instrumenting the callable itself. ``same_thread=True`` counts only
    compilations made by the thread that entered the region: a training
    loop brackets its own dispatches while an evaluator thread's first
    compile runs beside it.

        with RecompileSentinel() as sentinel:
            for _ in range(n):
                state, metrics = update(state, batch)
        sentinel.assert_clean()
    """

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, same_thread: bool = False):
        self.compilations = 0
        self._active = False
        self._same_thread = same_thread
        self._thread: int | None = None

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        # jax compiles synchronously on the dispatching thread and
        # records the event there, so the listener's thread IS the
        # compiling thread
        if (self._active and event == self._EVENT
                and (not self._same_thread
                     or threading.get_ident() == self._thread)):
            self.compilations += 1

    def __enter__(self) -> "RecompileSentinel":
        from jax._src import monitoring

        self._thread = threading.get_ident()

        monitoring.register_event_duration_secs_listener(self._on_event)
        self._active = True
        return self

    def __exit__(self, *exc) -> None:
        self._active = False
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)

    def assert_clean(self, what: str = "steady-state region") -> None:
        if self.compilations:
            raise RecompileError(
                f"{what} triggered {self.compilations} XLA compilation(s) "
                "after warmup — a static-shape or weak-type mismatch is "
                "defeating the jit cache")


class ReshardError(AssertionError):
    """A path that must keep one layout compiled to resharding copies."""


class ReshardSentinel:
    """Counts resharding collectives in a jitted callable's compiled HLO.

    The static ``sharding-spec-drift`` family flags trees that the SOURCE
    places under two different partition factories; this sentinel is its
    dynamic twin — it reads what XLA actually compiled.  A clean fused
    learner path contains gradient ``all-reduce``s (expected: that IS
    data parallelism) but no ``all-to-all`` or ``collective-permute``:
    those only appear when GSPMD had to move a tree between layouts
    mid-program, i.e. an implicit reshard paying a full device-to-device
    copy every step.

        sentinel = ReshardSentinel()
        sentinel.inspect(fn, *warmup_args)   # fn.lower(...).compile()
        sentinel.assert_clean("fused learner path")
        assert sentinel.steady_state_reshards == 0
    """

    # Ops that MOVE data between layouts.  all-reduce/all-gather are
    # deliberately absent: gradient reduction and merge broadcasts are
    # the collectives the program is SUPPOSED to contain.
    _RESHARD_OPS = ("all-to-all", "collective-permute")

    def __init__(self):
        self.reshards = 0
        self.ops: dict[str, int] = {}

    @property
    def steady_state_reshards(self) -> int:
        return self.reshards

    def inspect(self, fn, *args, **kwargs) -> int:
        """Lower+compile ``fn`` for ``args`` and scan the HLO text.
        ``lower`` never executes (and never consumes donated buffers), so
        this is safe to run against live training state."""
        lowered = fn.lower(*args, **kwargs)
        try:
            text = lowered.compile().as_text()
        except Exception:  # backends without compiled-text introspection
            text = lowered.as_text()
        return self.inspect_text(text)

    def inspect_text(self, hlo_text: str) -> int:
        found = 0
        for op in self._RESHARD_OPS:
            n = hlo_text.count(op)
            if n:
                self.ops[op] = self.ops.get(op, 0) + n
                found += n
        self.reshards += found
        # same unified ledger as the other sentinels: bench artifacts and
        # the fleet report read one counter instead of private copies
        from d4pg_tpu.obs.registry import REGISTRY

        REGISTRY.counter("profiling.reshards").inc(found)
        return found

    def assert_clean(self, what: str = "steady-state path") -> None:
        if self.reshards:
            detail = ", ".join(f"{op} x{n}"
                               for op, n in sorted(self.ops.items()))
            raise ReshardError(
                f"{what} compiled to {self.reshards} resharding "
                f"collective(s) ({detail}) — a tree is produced under one "
                f"sharding spec and consumed under another; route both "
                f"through the same parallel/partition.py factory")


class TransferSentinel:
    """Counts explicit host<->device transfers in the bracketed region.

    Patches ``jax.device_put`` / ``jax.device_get`` for the duration of
    the context and tallies calls (``h2d`` / ``d2h``). Implicit transfers
    (``np.asarray`` on a device array, scalar coercion) bypass those entry
    points; pass ``guard="disallow"`` to make jax raise on them instead —
    note the guard is inert on the CPU backend, where host and device
    memory are one and the same.

        with TransferSentinel() as t:
            run_fused_chunk()
        assert t.total == 0
    """

    def __init__(self, guard: str | None = None):
        self.h2d = 0
        self.d2h = 0
        self._guard = guard
        self._stack: contextlib.ExitStack | None = None

    @property
    def total(self) -> int:
        return self.h2d + self.d2h

    def __enter__(self) -> "TransferSentinel":
        import jax

        self._orig_put, self._orig_get = jax.device_put, jax.device_get

        def counted_put(*a, **kw):
            self.h2d += 1
            return self._orig_put(*a, **kw)

        def counted_get(*a, **kw):
            self.d2h += 1
            return self._orig_get(*a, **kw)

        jax.device_put, jax.device_get = counted_put, counted_get
        self._stack = contextlib.ExitStack()
        if self._guard:
            self._stack.enter_context(jax.transfer_guard(self._guard))
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.device_put, jax.device_get = self._orig_put, self._orig_get
        if self._stack is not None:
            self._stack.close()
            self._stack = None
