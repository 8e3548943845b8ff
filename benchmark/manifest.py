"""``BENCHMARK.json`` as the harness reads it, and the one validator every
result line passes before it is written (the tests check printed lines with
the same function)."""

from __future__ import annotations

import json
import math
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class LineError(ValueError):
    """The result object does not meet the contract; nothing is printed."""


def load(path: str | None = None) -> dict:
    with open(path or os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in manifest['workloads']]}")


def metrics_for(manifest: dict, cell_name: str, trace: bool) -> dict:
    """name -> entry of the metrics this cell reports in this mode: its
    end-to-end metrics untraced, its per-layer metrics traced. A metric
    without a ``workloads`` key belongs to every cell."""
    entries = manifest["per_layer" if trace else "end_to_end"]
    return {m["name"]: m for m in entries
            if cell_name in m.get("workloads", [cell_name])}


def _number(x, what: str) -> None:
    if type(x) not in (int, float) or isinstance(x, bool):
        raise LineError(f"{what} is {type(x).__name__} {x!r}, not a plain "
                        "Python number")
    if not math.isfinite(x):
        raise LineError(f"{what} is not finite: {x!r}")


def validate_line(manifest: dict, cell_name: str, trace: bool, obj: dict,
                  platform: str = "tpu") -> None:
    """Raise ``LineError`` unless ``obj`` is a result line of this cell in
    this mode: the keys the driver reads, every metric of the cell a finite
    plain float with its unit and none besides, the device the cell asks
    for, and traced ``0 < busy_s <= window_s``."""
    w = cell(manifest, cell_name)
    need = {"correct", "attempted", "failed", "metrics", "device"}
    if not need <= set(obj):
        raise LineError(f"missing keys {sorted(need - set(obj))}")
    if type(obj["correct"]) is not bool:
        raise LineError("correct is not a bool")
    for key in ("attempted", "failed"):
        if type(obj[key]) is not int or obj[key] < 0:
            raise LineError(f"{key} is not a non-negative int: {obj[key]!r}")
    if obj["failed"] > obj["attempted"]:
        raise LineError("failed exceeds attempted")
    want = metrics_for(manifest, cell_name, trace)
    got = obj["metrics"]
    if set(got) != set(want):
        raise LineError(f"metrics differ from the cell's: missing "
                        f"{sorted(set(want) - set(got))}, extra "
                        f"{sorted(set(got) - set(want))}")
    for name, m in got.items():
        if set(m) != {"value", "unit"}:
            raise LineError(f"metric {name} has keys {sorted(m)}")
        _number(m["value"], f"metric {name}")
        if type(m["value"]) is not float:
            raise LineError(f"metric {name} is not a float")
        if m["unit"] != want[name]["unit"]:
            raise LineError(f"metric {name} has unit {m['unit']!r}, the "
                            f"manifest says {want[name]['unit']!r}")
        if not trace and m["value"] <= 0:
            raise LineError(f"end-to-end metric {name} is not above 0")
    dev = obj["device"]
    dev_keys = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        dev_keys |= {"busy_s", "window_s"}
    if not dev_keys <= set(dev):
        raise LineError(f"device lacks {sorted(dev_keys - set(dev))}")
    if dev["platform"] != platform:
        raise LineError(f"device platform is {dev['platform']!r}, want "
                        f"{platform!r}")
    if type(dev["count"]) is not int or dev["count"] != w["chips"]:
        raise LineError(f"device count {dev['count']!r} is not the cell's "
                        f"chips {w['chips']}")
    if type(dev["memory_peak_bytes"]) is not int \
            or dev["memory_peak_bytes"] <= 0:
        raise LineError("memory_peak_bytes is not a positive int")
    if trace:
        _number(dev["busy_s"], "busy_s")
        _number(dev["window_s"], "window_s")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise LineError(f"busy_s {dev['busy_s']} is not in (0, "
                            f"window_s {dev['window_s']}]")
    if "breakdown" in obj:
        bd = obj["breakdown"]
        for key in ("device_ops", "idle_gaps"):
            rows = bd.get(key, [])
            if len(rows) > 10:
                raise LineError(f"breakdown.{key} has over 10 entries")
            for row in rows:
                if len(row) != 2 or type(row[0]) is not str:
                    raise LineError(f"breakdown.{key} row {row!r}")
                _number(row[1], f"breakdown.{key} {row[0]}")
    try:
        json.loads(json.dumps(obj, allow_nan=False))
    except ValueError as e:
        raise LineError(f"not strict JSON: {e}") from e
