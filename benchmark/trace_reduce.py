"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics and the ``device`` block read. Read with nothing but
``jax.profiler.ProfileData``.

What is read, and why:

- the device plane ``/device:TPU:<n>``. Its ``XLA Ops`` line holds one
  event per executed HLO operation on the TensorCore; ``XLA Modules`` holds
  one event per executed program. The lines overlap in time (a module
  spans its ops; ``Steps`` spans modules), so busy time is the *union* of
  the intervals of the ops line alone, clipped to the window — never a sum
  over lines.
- the host plane's ``bench.*`` events: ``jax.profiler.TraceAnnotation``s
  the harness puts round its own calls. ``bench.window`` brackets the
  traced window; the others say what the host was doing in an idle gap.
- on the CPU backend (the tests' rehearsal) there is no device plane: XLA's
  CPU client writes op events, tagged with ``hlo_module``, on host-plane
  thread lines. They are read as the ops line, and programs are
  reconstructed from them, so the same code path runs without a chip.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re

import numpy as np

WINDOW = "bench.window"
HOST_PREFIX = "bench."
# control-flow operations span the operations of their bodies on the same
# line; they are left out of the list of operations, not out of busy time
_CONTAINER = re.compile(r"^%?(while|conditional|call)[.\d]*( |$)")
NAME_CHARS = 96  # the trace names an operation by its whole HLO text


@dataclasses.dataclass
class Trace:
    """Times in seconds on the trace's own clock."""
    window: tuple[float, float]
    op_names: list  # per op event
    op_start: np.ndarray
    op_end: np.ndarray
    mod_names: list  # per program execution
    mod_start: np.ndarray
    mod_end: np.ndarray
    host: list  # (name, start, end) of bench.* annotations
    n_device_planes: int


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _cpu_ops(plane):
    """Op events of the CPU client: any host-line event that carries an
    ``hlo_module`` stat, as (name, start, end, module) by start time."""
    ops = []
    for line in plane.lines:
        for e in line.events:
            stats = dict(e.stats)
            if "hlo_module" in stats and not e.name.startswith("end: "):
                ops.append((e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                            str(stats["hlo_module"])))
    ops.sort(key=lambda o: o[1])
    return ops


def _cpu_modules(ops):
    """One program execution = a run of one module's ops that starts where
    the module's first-seen op name comes round again."""
    first, runs = {}, {}
    mods = []
    for name, start, end, mod in ops:
        if mod not in first:
            first[mod] = name
        if name == first[mod] or mod not in runs:
            runs[mod] = [mod, start, end]
            mods.append(runs[mod])
        else:
            runs[mod][2] = max(runs[mod][2], end)
    return [tuple(m) for m in mods]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):  # the recorded fixture is kept compressed
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device = sorted((p for p in data.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    host_planes = [p for p in data.planes if p.name.startswith("/host:")]
    host = []
    for plane in host_planes:
        for line in plane.lines:
            host += [ev for ev in _events(line)
                     if ev[0].startswith(HOST_PREFIX)]
    if device:
        # the first chip's TensorCore; a one-chip cell has one
        lines = {line.name: line for line in device[0].lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            raise ValueError(f"device plane {device[0].name} has lines "
                             f"{sorted(lines)}; want XLA Ops and XLA Modules")
        ops = _events(lines["XLA Ops"])
        mods = _events(lines["XLA Modules"])
    else:
        cpu = []
        for plane in host_planes:
            cpu += _cpu_ops(plane)
        cpu.sort(key=lambda o: o[1])
        ops = [o[:3] for o in cpu]
        mods = _cpu_modules(cpu)
    windows = [ev for ev in host if ev[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"want one {WINDOW} annotation, found "
                         f"{len(windows)}")
    ops.sort(key=lambda o: o[1])
    mods.sort(key=lambda m: m[1])
    return Trace(
        window=(windows[0][1], windows[0][2]),
        op_names=[o[0] for o in ops],
        op_start=np.asarray([o[1] for o in ops], np.float64),
        op_end=np.asarray([o[2] for o in ops], np.float64),
        mod_names=[m[0] for m in mods],
        mod_start=np.asarray([m[1] for m in mods], np.float64),
        mod_end=np.asarray([m[2] for m in mods], np.float64),
        host=[ev for ev in host if ev[0] != WINDOW],
        n_device_planes=len(device))


def union_intervals(start: np.ndarray, end: np.ndarray, lo: float,
                    hi: float) -> tuple[np.ndarray, np.ndarray]:
    """Merged, sorted intervals of ``[start, end)`` clipped to ``[lo, hi]``."""
    s, e = np.clip(start, lo, hi), np.clip(end, lo, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    run_end = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > run_end[:-1]])
    starts = s[new]
    ends = np.concatenate([run_end[:-1][new[1:]], run_end[-1:]])
    return starts, ends


def busy_and_window(trace: Trace) -> tuple[float, float]:
    """(busy_s, window_s): the union of op intervals inside the window, and
    the window's length."""
    lo, hi = trace.window
    s, e = union_intervals(trace.op_start, trace.op_end, lo, hi)
    return float(np.sum(e - s)), float(hi - lo)


def program_runs(trace: Trace, prefix: str) -> tuple[np.ndarray, np.ndarray]:
    """(start, end) of every execution inside the window of the program
    whose name starts with ``prefix`` (``jit_fn`` is the fused chunk)."""
    lo, hi = trace.window
    pick = [i for i, n in enumerate(trace.mod_names) if n.startswith(prefix)
            and trace.mod_start[i] >= lo and trace.mod_end[i] <= hi]
    return trace.mod_start[pick], trace.mod_end[pick]


def top_ops(trace: Trace, n: int = 8) -> list:
    """The ``n`` operations with most device time in the window, same-named
    events summed: ``[[name, seconds], ...]``."""
    lo, hi = trace.window
    dur = np.clip(trace.op_end, lo, hi) - np.clip(trace.op_start, lo, hi)
    total: dict = {}
    for name, d in zip(trace.op_names, dur):
        if d > 0 and not _CONTAINER.match(name):
            total[name] = total.get(name, 0.0) + float(d)
    return [[k[:NAME_CHARS], v] for k, v in sorted(
        total.items(), key=lambda kv: -kv[1])[:n]]


def idle_by_host(trace: Trace, n: int = 8) -> list:
    """Idle time inside the window, attributed to the innermost ``bench.*``
    annotation open at each gap's midpoint (``host.other`` where none is):
    ``[[name, seconds], ...]``, largest first."""
    lo, hi = trace.window
    s, e = union_intervals(trace.op_start, trace.op_end, lo, hi)
    gap_s = np.concatenate([[lo], e])
    gap_e = np.concatenate([s, [hi]])
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    mid = 0.5 * (gap_s + gap_e)
    owner = np.full(mid.shape, -1, np.int64)
    best = np.full(mid.shape, np.inf)
    # innermost = shortest covering annotation; few annotation kinds, many
    # gaps, so loop over annotations and vectorise over gaps
    order = np.argsort(mid)
    mid_sorted = mid[order]
    names = sorted({h[0] for h in trace.host})
    for name_i, name in enumerate(names):
        for _n, a, b in (h for h in trace.host if h[0] == name):
            i0, i1 = np.searchsorted(mid_sorted, [a, b])
            if i1 > i0:
                sel = order[i0:i1]
                better = (b - a) < best[sel]
                owner[sel[better]] = name_i
                best[sel[better]] = b - a
    total: dict = {}
    for i, d in zip(owner, gap_e - gap_s):
        key = names[i] if i >= 0 else "host.other"
        total[key] = total.get(key, 0.0) + float(d)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]
