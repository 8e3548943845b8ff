"""Run one cell once and print one result line.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no child. ``d4pg_tpu.startup.start()`` comes first: no chip is
a non-zero exit with no result line. The cell is an entry of ``workloads``
in ``BENCHMARK.json``; its configuration, traffic mix, driver and per-layer
readers are files found by the names there (see PERF.md, "Adding a cell").

The result line. ``write_result`` is the only thing that writes to the
process's real stdout: file descriptor 1 is pointed at stderr for the whole
run, so whatever the program prints (``[startup]``, thread logs, profiler
messages) cannot follow or precede the line. The object is validated against
the cell's entry in ``BENCHMARK.json`` first; after the line the process
flushes and ``os._exit(0)``s, so no ``atexit`` hook or daemon thread runs. A
failed check, a percentile with too few samples, a non-finite number or any
exception exits non-zero with the reason on stderr and no line.

``--rehearsal 1`` (tests only) takes the tiny sizes of each file's
``rehearsal`` block and the CPU backend; the line then names platform
``cpu``. ``--fault`` (rehearsal only) injects the failures the tests must
see refused.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

from benchmark import manifest as manifest_mod  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))
FAULTS = ("", "nan_loss", "no_samples", "unknown_kind", "frozen_step")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
REHEARSAL_PLATFORM = "cpu"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def take_stdout() -> int:
    """Keep the real stdout as a private descriptor and point fd 1 (and
    ``sys.stdout``) at stderr."""
    sys.stdout.flush()
    real = os.dup(1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return real


def write_result(fd: int, man: dict, cell_name: str, trace: bool, obj: dict,
                 platform: str) -> None:
    """Validate, then write the one line and leave at once."""
    manifest_mod.validate_line(man, cell_name, trace, obj, platform=platform)
    line = json.dumps(obj, allow_nan=False) + "\n"
    sys.stderr.flush()
    os.write(fd, line.encode())
    os._exit(0)


def load_peak(kind: str) -> dict:
    with open(os.path.join(ROOT, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"device_kind {kind!r} is not in benchmark/peaks.json "
                       f"(it has {sorted(k for k in peaks if k != 'source')})")
    return peaks[kind]


def layer_reader(name: str):
    """``benchmark/layer_metrics/<name>.py``'s ``read``."""
    path = os.path.join(ROOT, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearsal", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS, default="")
    args = ap.parse_args(argv)
    if args.fault and not args.rehearsal:
        ap.error("--fault is for rehearsals")
    out_fd = take_stdout()
    trace, rehearsal = bool(args.trace), bool(args.rehearsal)

    man = manifest_mod.load()
    cell = manifest_mod.cell(man, args.workload)

    from d4pg_tpu import startup

    compile_s = [0.0]

    def on_compile(event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            compile_s[0] += duration

    from jax._src import monitoring

    monitoring.register_event_duration_secs_listener(on_compile)
    want_platform = REHEARSAL_PLATFORM if rehearsal else "tpu"
    device = startup.start(want_platform)
    if device["platform"] != want_platform:
        log(f"[bench] backend is {device['platform']!r}, want "
            f"{want_platform!r}: no accelerator")
        return 2
    if device["count"] < cell["chips"]:
        log(f"[bench] {device['count']} device(s), the cell asks for "
            f"{cell['chips']}")
        return 2

    from benchmark import cellbuild, shapes
    from benchmark.learner import RunEnv

    cfg = cellbuild.load_config(cell["config"], rehearsal)
    traffic = cellbuild.load_traffic(cell["traffic"], rehearsal)
    # an unknown device is an error, not a default; the CPU rehearsal reads
    # the table's labelled stand-in so that the roofline reader still runs
    kind = "rehearsal" if rehearsal else device["kind"]
    peak = load_peak("no-such-device" if args.fault == "unknown_kind"
                     else kind)
    env = RunEnv(cell=cell, cfg=cfg, traffic=traffic, seed=args.seed,
                 seconds=args.seconds, trace=trace, rehearsal=rehearsal,
                 fault=args.fault, t_start=_T_START,
                 trace_dir=os.path.join(manifest_mod.REPO, ".bench_trace"),
                 wanted=frozenset(manifest_mod.metrics_for(
                     man, args.workload, trace)),
                 compile_seconds=lambda: compile_s[0], log=log)
    if trace:
        shutil.rmtree(env.trace_dir, ignore_errors=True)
    driver = importlib.import_module("benchmark.drivers." + traffic["driver"])
    result = driver.run(env)

    metrics = {}
    breakdown = None
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": result["memory_peak_bytes"]}
    if trace:
        from benchmark import trace_reduce

        tr = trace_reduce.load(trace_reduce.newest_xplane(env.trace_dir))
        busy, window_s = trace_reduce.busy_and_window(tr)
        dev["busy_s"], dev["window_s"] = busy, window_s
        ctx = dict(result["layer_ctx"])
        ctx.update(trace=tr, peak=peak, counts=shapes.step_counts(cfg))
        for name, entry in manifest_mod.metrics_for(
                man, args.workload, True).items():
            value = layer_reader(name)(ctx)
            if value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": entry["unit"]}
        breakdown = {"device_ops": trace_reduce.top_ops(tr),
                     "idle_gaps": trace_reduce.idle_by_host(tr)}
    else:
        for name, entry in manifest_mod.metrics_for(
                man, args.workload, False).items():
            if name in result["end_to_end"]:
                metrics[name] = {"value": float(result["end_to_end"][name]),
                                 "unit": entry["unit"]}
    obj = {"correct": bool(result["correct"]),
           "attempted": result["attempted"], "failed": result["failed"],
           "metrics": metrics, "device": dev}
    if breakdown is not None:
        obj["breakdown"] = breakdown
    log("[bench] " + json.dumps(obj))
    if not obj["correct"]:
        log("[bench] correct=false: a compared number exceeds its limit; "
            "no result line")
        return 3
    write_result(out_fd, man, args.workload, trace, obj, want_platform)
    return 0  # not reached


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 2
    except BaseException:  # noqa: BLE001 - report, then leave without a line
        traceback.print_exc(file=sys.stderr)
        code = 1
    sys.stderr.flush()
    # daemon threads of the program may still be alive; none may print
    os._exit(code if code else 1)
