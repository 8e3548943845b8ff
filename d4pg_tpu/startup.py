"""Process start-up: the one backend rule every entry point shares.

``start()`` runs before the first JAX call that initialises a backend,
in ``d4pg_tpu.train.main``, ``__graft_entry__.entry`` and
``chip_smoke.py``. The rule has two outcomes and nothing in between:

  - chip (the default): the TPU is required. ``jax_platforms`` becomes
    ``tpu,cpu`` and an initialisation failure raises — there is no "found
    no chip, carrying on". The CPU backend is registered BEHIND the TPU
    because actor and evaluator inference pin themselves to
    ``jax.local_devices(backend="cpu")[0]`` (``serving/client.py``,
    default ``actor_device="cpu"``); with ``tpu`` alone that backend would
    never exist.
  - CPU, by explicit request only: ``platform="cpu"`` (``--platform
    cpu``), or a ``JAX_PLATFORMS`` the caller set (the tier-1 command and
    the multi-host tests set ``cpu``). A caller-set list decides the
    default backend as given; ``cpu`` is appended when it lacks it, for
    the same pinned-inference reason.

The persistent compile cache is placed from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
set here; otherwise the cache lives at ONE fixed path inside the checkout
(the path is part of the cache key — a directory that moves never hits).
Every program is cached, not only the slow-to-compile ones.
Library code (``train()``) and the test suite never call this, so a
tier-1 run leaves the in-checkout cache empty.

One process per chip: a process that has called ``start()`` on the chip
holds it, and must not start a child that needs it.

The start-up log (``obs/startup_log.py``) gets its jax half here, since
``obs/`` imports none: ``configure`` installs, once a process and before the
first trace, one ``jax.monitoring`` duration listener and one event listener
that write the compile pipeline's events into the log (``compile.trace``,
``compile.lower``, ``compile.backend`` with the function's name;
``cache.request``, ``cache.hit``, ``cache.load``), each beneath whatever
span is open on its thread, and one scalar listener beside them: jax
reports every jitted function traced inside another (34,000 events in the
first trace of one torso chunk) and every helper a kernel's lowering rule
traces, announces the start of each trace and lowering by a scalar of the
event's name, and so the outermost of a thread is the one that ends with
none open; only that one is kept (the union of their intervals, which is
what ``trace_lower_s`` reads, loses nothing). ``configure`` and ``describe``'s
``jax.devices()`` are the phases ``startup.configure`` and
``startup.backend``; both import jax before their span opens, so a first
``import jax`` is ``import.jax``'s and not theirs. The listeners stay for the
life of the process: once the log is full each event costs one compare.
"""

from __future__ import annotations

import os
import threading
from importlib import metadata

from d4pg_tpu.obs import trace
from d4pg_tpu.obs.startup_log import LOG

PLATFORMS = ("tpu", "cpu")
# jax 0.9.0's names (jax/_src/dispatch.py, compiler.py) -> the log's
_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache.load",
}
_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache.request",
    "/jax/compilation_cache/cache_hits": "cache.hit",
}
# tracing and lowering nest (a jitted function traced inside another; a
# kernel's lowering rules tracing their helpers): see ``_on_scalar``
_NESTING = frozenset({"/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration"})
_tracing = threading.local()  # .depth: such events open on this thread
_listening = False

# <checkout>/.jax_cache — resolved from the package location, never a
# temporary name, a pid or a timestamp
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def _on_scalar(event: str, _value, **_stats) -> None:
    """A trace or a lowering begins (jax says so by a scalar of the
    duration's name)."""
    if event in _NESTING:
        _tracing.depth = getattr(_tracing, "depth", 0) + 1


def _on_duration(event: str, seconds: float, **stats) -> None:
    name = _DURATIONS.get(event)
    if name is None:
        return
    if event in _NESTING:
        _tracing.depth = depth = max(0, getattr(_tracing, "depth", 1) - 1)
        if depth:  # inside another: its seconds are the outer one's
            return
    LOG.add(name, seconds, **stats)


def _on_event(event: str, **stats) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        LOG.add(name, **stats)


def configure(platform: str = "tpu") -> None:
    """Apply the backend rule and place the compile cache WITHOUT
    initialising a backend — the half a multi-host process runs before
    ``jax.distributed.initialize`` (which must precede backend init)."""
    global _listening
    if platform not in PLATFORMS:
        raise ValueError(f"unknown platform {platform!r} (want {PLATFORMS})")
    import jax

    with trace.span("startup.configure"):
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_scalar_listener(_on_scalar)
            _listening = True
        requested = os.environ.get("JAX_PLATFORMS", "")
        if platform == "cpu":
            platforms = "cpu"
        elif requested:
            names = requested.split(",")
            platforms = requested if "cpu" in names else requested + ",cpu"
        else:
            platforms = "tpu,cpu"
        jax.config.update("jax_platforms", platforms)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
            # jax's default keeps only programs that took >= 1 s to compile:
            # 3 of the smoke's ~108. Caching all of them took the smoke's
            # second run on one machine from 38.5 s to 24.3 s in train.main
            # (chip runs, PR 21; CHANGES.md).
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)


def describe() -> dict:
    """Initialise the backends (raising when the requested one cannot)
    and print the start-up line every entry point shares. Returns what
    the line says: ``{"platform", "kind", "count", "jax", "jaxlib",
    "libtpu"}`` with the device as JAX reports it."""
    import jax
    import jaxlib

    with trace.span("startup.backend"):
        devices = jax.devices()
    # the backend is up: the program's host spans now go to the profiler
    # (obs/ imports no jax, so the annotation class is handed to it here)
    trace.set_annotator(jax.profiler.TraceAnnotation)
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only install
        libtpu = None
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }
    print(f"[startup] platform={info['platform']} "
          f"device_kind={info['kind']!r} devices={info['count']} "
          f"jax={info['jax']} jaxlib={info['jaxlib']} libtpu={libtpu} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}",
          flush=True)
    return info


def start(platform: str = "tpu") -> dict:
    """``configure`` + ``describe``: what a single-process entry point
    calls before its first JAX computation."""
    configure(platform)
    return describe()
