"""What a stalled host costs a cell: run the benchmark's own command as a
child and, once its window is open, stop the whole process (SIGSTOP) for
``stall_s`` seconds every ``every_s`` seconds, as the shared host of a
one-chip machine does to it unasked. This process never touches JAX.

    python -m benchmark.tools.stall_probe <workload> <seed> <seconds> \
        <stall_s> <every_s> [<queue_ahead_s>|- [<more arguments>]]

With ``queue_ahead_s`` the run is made from a copy of the checkout (under
``.scratch/``, which ``.gitignore`` lists) whose traffic file carries that
value, so that one call can set a deep queue beside a queue of one chunk.
Prints the child's ``[window]`` lines and its result line. PERF.md records
what PR 24 read with it.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

from benchmark import manifest

SKIP = shutil.ignore_patterns(".git", "chiprun_out", ".jax_cache", ".scratch",
                              ".bench_trace", "__pycache__", "runs")


def checkout_with(queue_ahead_s: float, traffic: str) -> str:
    """A copy of the checkout whose traffic file has ``queue_ahead_s``."""
    root = os.path.join(manifest.REPO, ".scratch",
                        f"probe-q{queue_ahead_s:g}")
    shutil.rmtree(root, ignore_errors=True)
    shutil.copytree(manifest.REPO, root, ignore=SKIP)
    path = os.path.join(root, "benchmark", "traffic", traffic + ".json")
    with open(path) as f:
        data = json.load(f)
    data["queue_ahead_s"] = queue_ahead_s
    with open(path, "w") as f:
        json.dump(data, f)
    return root


def main(argv) -> int:
    workload, seed, seconds = argv[0], argv[1], argv[2]
    stall_s, every_s = float(argv[3]), float(argv[4])
    man = manifest.load()
    root = manifest.REPO
    if len(argv) > 5 and argv[5] != "-":
        root = checkout_with(float(argv[5]),
                             manifest.cell(man, workload)["traffic"])
    cmd = [sys.executable, *man["command"][1:], "--workload", workload,
           "--seed", seed, "--seconds", seconds, "--trace", "0", *argv[6:]]
    child = subprocess.Popen(cmd, cwd=root, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
    opened = threading.Event()

    def follow() -> None:
        for line in child.stderr:
            if line.startswith(("[window]", "[setup]", "[ingest]")):
                print(line, end="", flush=True)
            if "kept queued" in line:  # printed as the window opens
                opened.set()
        opened.set()

    reader = threading.Thread(target=follow, daemon=True)
    reader.start()
    opened.wait()
    stalls = 0
    # the last stall ends a second before the window does, so that none
    # falls on the check that follows it
    t_open = time.perf_counter()
    while (stall_s > 0 and child.poll() is None and time.perf_counter()
           - t_open + every_s + stall_s < float(seconds) - 1.0):
        time.sleep(every_s)
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(stall_s)
        os.kill(child.pid, signal.SIGCONT)
        stalls += 1
    out = child.stdout.read()
    code = child.wait()
    reader.join(timeout=5.0)
    print(f"[probe] {stalls} stalls of {stall_s} s; exit {code}", flush=True)
    print(out.strip().splitlines()[-1] if out.strip() else "(no result line)",
          flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
