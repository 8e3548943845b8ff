"""Startup micro-autotuner for the categorical-projection implementation.

The best ``--projection`` variant is an empirical fact of the (batch,
atoms, chip) triple, not something a default can know. ``--projection
auto`` (the config default) times the candidates ON THE ACTUAL SHAPES at
startup and picks the winner; an explicit ``--projection einsum|pallas|
pallas_ce`` remains the escape hatch and is honored verbatim.

``auto`` never hides a compile error: a candidate that fails to lower
loses the race, but its error is carried in full in the ``[autotune]``
line, and every candidate failing is an error, not a quiet ``einsum``.

What gets timed: the critic-loss core each variant actually changes —
``value_and_grad`` of the projected-Bellman cross-entropy at [B, A]
(projection forward for einsum/pallas, the fused forward+custom-VJP for
pallas_ce) — under jit, warmed up, best-of-``repeats`` wall time. The
surrounding network passes are identical across variants and would only
dilute the signal.

Static policy short-circuits (no timing, reason recorded):

  - non-TPU backends: CPU runs Pallas in interpret mode (measures the
    emulator, not a kernel) and other backends have no Pallas lowering —
    einsum is the only real candidate either way;
  - mesh/multi-host learners: the Pallas kernels have no GSPMD
    partitioning rule (``parallel/data_parallel.check_mesh_compatible``
    rejects them), so einsum is the only legal candidate.

Results are cached per (batch, support, backend) so repeated learner
builds in one process autotune once; the selection is logged once with
its timings so run logs name the variant actually compiled in.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

CANDIDATES = ("einsum", "pallas", "pallas_ce")


@dataclasses.dataclass(frozen=True)
class AutotuneResult:
    selected: str
    reason: str
    timings_ms: dict | None = None  # per-candidate best step time (None =
    #                                 static policy, nothing was timed)

    def as_json(self) -> dict:
        return {"selected": self.selected, "reason": self.reason,
                "timings_ms": self.timings_ms}


_CACHE: dict[tuple, AutotuneResult] = {}
_LOGGED: set[tuple] = set()

# Unified arbitration ledger: every select_* surface records its latest
# decision here, and bench.py persists the WHOLE ledger as ONE
# schema-versioned ``autotune`` block (autotune_block) instead of each
# surface ad-hoc logging its own key. Keyed by surface name
# ('projection', 'sampler', ...); latest selection wins.
AUTOTUNE_SCHEMA = 1
_SURFACES: dict[str, AutotuneResult] = {}


def _record(surface: str, result: AutotuneResult) -> AutotuneResult:
    _SURFACES[surface] = result
    return result


def autotune_block() -> dict:
    """The bench artifact's ``autotune`` block: chosen arm + timings for
    every arbitration surface that ran this process, one schema under
    one key (the satellite-2 contract; tests/test_devsample.py pins the
    shape)."""
    return {
        "metric": "autotune",
        "schema": AUTOTUNE_SCHEMA,
        "surfaces": {name: r.as_json() for name, r in _SURFACES.items()},
    }


def _loss_fn(variant: str, support, interpret: bool):
    import jax

    from d4pg_tpu.core.distribution import categorical_projection
    from d4pg_tpu.core.losses import categorical_td_loss, weighted_mean

    if variant == "pallas_ce":
        from d4pg_tpu.ops.projection_ce import projection_ce_pallas

        def loss(pred, tp, r, d):
            td = projection_ce_pallas(support, tp, r, d, pred, interpret)
            return weighted_mean(td, None)

        return loss

    if variant == "pallas":
        from d4pg_tpu.ops.projection import projection_pallas

        def project(tp, r, d):
            return projection_pallas(support, tp, r, d, interpret)
    else:
        def project(tp, r, d):
            return categorical_projection(support, tp, r, d)

    def loss(pred, tp, r, d):
        proj = jax.lax.stop_gradient(project(tp, r, d))
        return categorical_td_loss(proj, pred)[0]

    return loss


def _time_variant(variant: str, support, batch_size: int,
                  repeats: int, iters: int) -> float:
    """Best-of-``repeats`` wall time (ms) of one jitted grad step of the
    variant's loss core at [batch_size, n_atoms]."""
    import jax
    import jax.numpy as jnp

    interpret = jax.default_backend() == "cpu"
    rng = np.random.default_rng(0)
    a = support.n_atoms
    tp = rng.random((batch_size, a)).astype(np.float32)
    tp /= tp.sum(-1, keepdims=True)
    pred = jnp.asarray(tp)
    tp = jnp.asarray(tp)
    r = jnp.asarray(rng.standard_normal(batch_size).astype(np.float32))
    d = jnp.full((batch_size,), 0.99, jnp.float32)

    step = jax.jit(jax.value_and_grad(_loss_fn(variant, support, interpret)))
    v, g = step(pred, tp, r, d)  # warmup/compile
    jax.block_until_ready(g)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            v, g = step(pred, tp, r, d)
        jax.block_until_ready(g)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def autotune_projection(batch_size: int, v_min: float, v_max: float,
                        n_atoms: int, repeats: int = 3,
                        iters: int = 20) -> AutotuneResult:
    """Time every candidate at the given shapes on the live backend and
    return the winner. TPU-only by policy (see module docstring) — the
    caller gates; this function times whatever backend is active."""
    from d4pg_tpu.core.distribution import CategoricalSupport

    support = CategoricalSupport(float(v_min), float(v_max), int(n_atoms))
    timings = {}
    for variant in CANDIDATES:
        try:
            timings[variant] = round(
                _time_variant(variant, support, batch_size, repeats, iters),
                4)
        except Exception as e:  # noqa: BLE001 — a kernel that fails to
            # lower loses the race; the [autotune] line prints its error
            timings[variant] = None
            timings[f"{variant}_error"] = f"{type(e).__name__}: {e}"
    timed = {k: v for k, v in timings.items() if isinstance(v, float)}
    if not timed:
        raise RuntimeError(
            f"projection autotune: every candidate failed at shape "
            f"[{batch_size}, {n_atoms}]: {timings}")
    best = min(timed, key=timed.get)
    return AutotuneResult(best, "measured fastest grad step at shape "
                          f"[{batch_size}, {n_atoms}]", timings)


def select_projection(flag: str, *, batch_size: int, v_min: float,
                      v_max: float, n_atoms: int,
                      mesh: bool = False) -> AutotuneResult:
    """Resolve a ``--projection`` flag to a concrete implementation.

    Explicit flags pass through untouched (the escape hatch); ``'auto'``
    applies the static policy, then measures when measuring is
    meaningful. Logs the selection (once per distinct choice) so every
    run names the variant it trains with."""
    if flag != "auto":
        return _record("projection",
                       AutotuneResult(flag, "explicit --projection override"))
    import jax

    backend = jax.default_backend()
    key = ("sel", batch_size, float(v_min), float(v_max), int(n_atoms),
           bool(mesh), backend)
    if key not in _CACHE:
        if mesh:
            result = AutotuneResult(
                "einsum", "mesh learner: Pallas kernels have no GSPMD "
                "partitioning rule (einsum is the only legal candidate)")
        elif backend != "tpu":
            result = AutotuneResult(
                "einsum", f"{backend} backend: Pallas would run in "
                "interpret/fallback mode — nothing real to time")
        else:
            result = autotune_projection(batch_size, v_min, v_max, n_atoms)
        _CACHE[key] = result
    result = _CACHE[key]
    log_key = (key, result.selected)
    if log_key not in _LOGGED:
        _LOGGED.add(log_key)
        timed = (f" timings_ms={result.timings_ms}"
                 if result.timings_ms else "")
        print(f"[autotune] projection='{result.selected}' "
              f"({result.reason}){timed}", flush=True)
    return _record("projection", result)


SAMPLER_ARMS = ("scan", "pallas", "host")


def autotune_sampler(capacity: int, k: int, batch_size: int,
                     repeats: int = 3, iters: int = 20) -> AutotuneResult:
    """Time the two DEVICE descent arms on the live backend at the real
    (capacity, K, B) shape — a synthetic tree with random positive
    priorities, [K*B] stratified queries — and return the faster. The
    'host' arm is never timed here: it is the PR-12 fallback the caller
    constructs when the device plane is unavailable, not a device
    candidate (the three-arm wall-clock A/B lives in bench.py's sampler
    block, where all three run the full wire-to-grad path)."""
    import jax
    import jax.numpy as jnp

    from d4pg_tpu.replay import device_per as dper
    from d4pg_tpu.ops.sampler_descent import descend_pallas, pallas_fits

    interpret = jax.default_backend() == "cpu"
    rng = np.random.default_rng(0)
    trees = dper.init(capacity)
    n = trees.capacity
    trees = dper.set_leaves_jitted(
        trees, jnp.arange(n),
        jnp.asarray(rng.random(n).astype(np.float32) + 1e-3))
    q = k * batch_size
    mass = jnp.asarray(
        (rng.random(q) * float(trees.sum_tree[1])).astype(np.float32))
    descend_scan = jax.jit(dper.descend)

    def _time(fn) -> float:
        out = fn()  # warmup/compile
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - t0) / iters)
        return best * 1e3

    timings: dict = {"scan": round(_time(
        lambda: descend_scan(trees.sum_tree, mass)), 4)}
    if pallas_fits(n):
        try:
            timings["pallas"] = round(_time(
                lambda: descend_pallas(trees.sum_tree, mass, interpret)), 4)
        except Exception as e:  # noqa: BLE001 — the kernel loses the
            # race; the [autotune] line prints its error in full
            timings["pallas"] = None
            timings["pallas_error"] = f"{type(e).__name__}: {e}"
    else:
        timings["pallas"] = None
        timings["pallas_error"] = (f"tree of {n} slots exceeds the VMEM "
                                   "residency budget")
    timed = {a: v for a, v in timings.items() if isinstance(v, float)}
    best = min(timed, key=timed.get)
    return AutotuneResult(best, "measured fastest descent at "
                          f"[{q}] queries over {n} slots", timings)


def select_sampler(flag: str, *, capacity: int, k: int,
                   batch_size: int) -> AutotuneResult:
    """Resolve a ``--sampler`` flag to a concrete sample-path arm —
    the third arbitration surface (after projection and projection_ce).

    Arms: ``'scan'`` (jnp gather descent on device), ``'pallas'``
    (VMEM-resident descent kernel, ``ops/sampler_descent``) and
    ``'host'`` (the PR-12 ``SampleDealer``, the fallback). Explicit
    flags pass through; ``'auto'`` applies the static policy — non-TPU
    backends fall back to 'host' (the fleet three-arm A/B shows the
    device arm's per-deal XLA dispatch saturating the CPU commit
    thread: deal→grad ~5× the host dealer's, wire→grad p95 pure
    queueing after that — and interpret-mode Pallas would measure the
    emulator, not the kernel), trees past the VMEM budget get 'scan' —
    and otherwise measures scan vs pallas. On TPU 'host' is never
    auto-selected: there the descent fuses into the commit dispatch the
    tree already lives behind, and the host arm would re-introduce the
    sampled-row H2D the device plane exists to delete."""
    if flag != "auto":
        if flag not in SAMPLER_ARMS:
            raise ValueError(f"unknown --sampler arm {flag!r} "
                             f"(want one of {('auto',) + SAMPLER_ARMS})")
        return _record("sampler",
                       AutotuneResult(flag, "explicit --sampler override"))
    import jax

    from d4pg_tpu.ops.sampler_descent import pallas_fits
    from d4pg_tpu.replay.segment_tree import next_pow2

    backend = jax.default_backend()
    key = ("sampler", int(capacity), int(k), int(batch_size), backend)
    if key not in _CACHE:
        if backend != "tpu":
            result = AutotuneResult(
                "host", f"{backend} backend: per-deal XLA dispatch "
                "saturates the commit thread off-accelerator (three-arm "
                "fleet A/B) — the PR-12 host dealer is the honest arm "
                "here; force --sampler scan/pallas to override")
        elif not pallas_fits(next_pow2(capacity)):
            result = AutotuneResult(
                "scan", f"tree of {next_pow2(capacity)} slots exceeds the "
                "Pallas kernel's VMEM residency budget")
        else:
            result = autotune_sampler(capacity, k, batch_size)
        _CACHE[key] = result
    result = _CACHE[key]
    log_key = (key, result.selected)
    if log_key not in _LOGGED:
        _LOGGED.add(log_key)
        timed = (f" timings_ms={result.timings_ms}"
                 if result.timings_ms else "")
        print(f"[autotune] sampler='{result.selected}' "
              f"({result.reason}){timed}", flush=True)
    return _record("sampler", result)
