"""A row's journey from ``ReplayService.add`` to the end of the first chunk
that can sample it, read from the program's own spans in a traced run.

The program says where a row stands (``d4pg_tpu/obs/trace.py``, PERF.md
section 3): ``ingest.admit`` says the admission ticket ``seq`` it gave;
``ingest.host_stage`` the tickets of its group (``seq_lo``, ``seq_hi``) and
the POSITION of the group's last row in the stream of rows pushed into host
staging (``through``); ``fused.stage_block`` the positions its block carries
(``first``, ``through``) and, through the multi-ring merge, the tickets
(``seq_lo``, ``seq_hi``); ``fused.commit_staged`` the same ``block``;
``learner.dispatch`` the position up to which rows have ``landed``. Host spans
and the device's ``XLA Modules`` line lie on one clock, the profiler's, so the
end of the chunk on the device closes the journey. For every add admitted in
``bench.window``:

    ingest.admit start --queue--> end of its ingest.host_stage
      --staging--> start of the fused.stage_block that carries it
      --inflight--> start of that block's fused.commit_staged
      --land_to_done--> end ON THE DEVICE of the chunk dispatched next

For a group of several adds the journey is that of the group's last row. A
row whose position falls in no block was dropped by the staging ring: counted,
never timed. An add whose later hops lie beyond the trace is still on its way:
counted, left out of the percentiles. Dispatches and executions of the chunk
program are matched in order from the trace's end, where the harness has
drained the queue, so a chunk dispatched before the profiler started cannot
shift the match.

Reuses ``program_trace.host_spans`` (through ``program_trace.analyse``, which
other readers of the cell have run already) and ``trace_reduce.Trace``. On a
program that does not say tickets and positions (the parent of PR 36, over
which the driver lays these files) every reader returns 0.0 and says so on
stderr: ``manifest.validate_line`` refuses a traced line that lacks a listed
metric.
"""

from __future__ import annotations

import bisect
import dataclasses

import numpy as np

from benchmark import program_trace, trace_reduce

HOPS = ("queue", "staging", "inflight", "land_to_done")
# metric -> its key in what ``analyse`` returns
METRICS = {
    "row_queue_ms.p95": "queue",
    "row_staging_ms.p95": "staging",
    "row_inflight_ms.p95": "inflight",
    "row_land_to_done_ms.p95": "land_to_done",
    "row_journey_ms.p95": "journey",
    "ingest_host_ms_per_chunk": "ingest_host",
    "lock_wait_ms.p95": "lock_wait",
}


def _named(spans: list, name: str) -> list:
    return [s for s in spans if s[0] == name]


def _holding(spans: list, lo: str, hi: str, value, also: str | None = None
             ) -> tuple | None:
    """The first of ``spans`` (in order) whose ``[lo, hi]`` stats hold
    ``value`` (and that has the stat ``also``)."""
    for s in spans:
        st = s[3]
        if lo in st and hi in st and st[lo] <= value <= st[hi] \
                and (also is None or also in st):
            return s
    return None


def pair_from_the_end(dispatches: list, trace: trace_reduce.Trace,
                      prefix: str) -> dict:
    """``id(dispatch span) -> (start, end)`` of its execution on the device:
    the trace's dispatches and its executions of the program ``prefix``,
    each in time order, paired last with last."""
    runs = sorted((trace.mod_start[i], trace.mod_end[i])
                  for i, n in enumerate(trace.mod_names)
                  if n.startswith(prefix))
    n = min(len(runs), len(dispatches))
    return {id(d): r for d, r in zip(dispatches[len(dispatches) - n:],
                                     runs[len(runs) - n:])}


def follow(spans: list, trace: trace_reduce.Trace, prefix: str) -> dict:
    """Every admitted add of the window through its hops: ``{"rows": [n, 5]
    boundary times (admit start, host_stage end, stage_block start,
    commit_staged start, the chunk's end on the device), "seqs": [n],
    "hook_at": [n] start of the ``learner.on_chunk`` after the commit (where
    the harness's ``admit_to_commit_ms`` ends), "dropped", "on_the_way":
    counts (an admitted add is followed, dropped or on its way), "pairs":
    dispatches paired with an execution,
    "dispatch_lead_s": the least time from a dispatch's start to its
    execution's (below 0: the device's events lie that much early on the
    host's clock), "late_landed": followed adds whose dispatch says a
    ``landed`` short of their position (0, or the two ties disagree)}``."""
    lo, hi = trace.window
    admits = [s for s in _named(spans, "ingest.admit")
              if "seq" in s[3] and lo <= s[1] <= hi]
    hosts = sorted(_named(spans, "ingest.host_stage"), key=lambda s: s[2])
    blocks = _named(spans, "fused.stage_block")
    commits = {s[3]["block"]: s for s in _named(spans, "fused.commit_staged")
               if "block" in s[3]}
    dispatches = _named(spans, "learner.dispatch")
    starts = [d[1] for d in dispatches]
    runs = pair_from_the_end(dispatches, trace, prefix)
    by_ticket = any("seq_lo" in b[3] for b in blocks)
    last_first = max((b[3]["first"] for b in blocks if "first" in b[3]),
                     default=0)
    hooks = [s[1] for s in _named(spans, "learner.on_chunk")]
    rows, seqs, hook_at = [], [], []
    dropped = on_the_way = late_landed = 0
    for a in admits:
        seq = a[3]["seq"]
        # by ticket (the multi-ring's direct stage): the shard worker's push
        # is the group's host staging, the first span to hold the ticket; by
        # position: the commit thread's, which says where the last row stands
        host = _holding(hosts, "seq_lo", "seq_hi", seq,
                        None if by_ticket else "through")
        if host is None:
            # admitted as the window closed: its group was staged after the
            # profiler stopped
            on_the_way += 1
            continue
        pos = None if by_ticket else host[3]["through"]
        block = (_holding(blocks, "seq_lo", "seq_hi", seq) if by_ticket
                 else _holding(blocks, "first", "through", pos))
        if block is None:
            # in no block: dropped if a later block exists, else not staged
            # when the trace ended (by ticket the two cannot be told apart:
            # it reads as on its way, and ``fused.rows_dropped`` counts it)
            if pos is not None and pos < last_first:
                dropped += 1
            else:
                on_the_way += 1
            continue
        commit = commits.get(block[3]["block"])
        i = bisect.bisect_left(starts, commit[1]) if commit else len(starts)
        run = runs.get(id(dispatches[i])) if i < len(starts) else None
        if run is None:
            on_the_way += 1
            continue
        if pos is not None and dispatches[i][3].get("landed", pos) < pos:
            late_landed += 1
        # the lock is released before the span closes: a block can start a
        # few microseconds before its group's host_stage has ended
        t = [a[1], min(host[2], block[1]), block[1], commit[1], run[1]]
        rows.append(t)
        seqs.append(seq)
        j = bisect.bisect_left(hooks, commit[1])
        hook_at.append(hooks[j] if j < len(hooks) else np.nan)
    lead = [runs[id(d)][0] - d[1] for d in dispatches if id(d) in runs]
    return {"rows": np.asarray(rows, np.float64).reshape(-1, 5),
            "hook_at": np.asarray(hook_at, np.float64),
            "dispatch_lead_s": min(lead, default=0.0),
            "seqs": seqs, "dropped": dropped, "on_the_way": on_the_way,
            "admitted": len(admits),
            "pairs": len(runs), "dispatches": len(dispatches),
            "late_landed": late_landed}


def hops_ms(rows: np.ndarray) -> dict:
    """The four hops and the journey of every followed add, in ms."""
    out = {h: 1e3 * (rows[:, i + 1] - rows[:, i])
           for i, h in enumerate(HOPS)}
    out["journey"] = 1e3 * (rows[:, 4] - rows[:, 0])
    return out


def per_chunk_host_ms(spans: list, window: tuple) -> np.ndarray:
    """``ingest.commit`` + ``ingest.stage`` inside each ``learner.chunk`` of
    the window, in ms."""
    lo, hi = window
    chunks = [s for s in _named(spans, "learner.chunk") if lo <= s[1] <= hi]
    calls = sorted((s[1], s[2] - s[1]) for s in spans
                   if s[0] in ("ingest.commit", "ingest.stage"))
    at = [c[0] for c in calls]
    return np.asarray([
        1e3 * sum(d for _s, d in calls[bisect.bisect_left(at, c[1]):
                                       bisect.bisect_right(at, c[2])])
        for c in chunks], np.float64)


def idle_outside_runs(trace: trace_reduce.Trace, spans: list) -> float:
    """Idle seconds of the window that lie outside every ``learner.run``."""
    runs = [s[:3] for s in _named(spans, "learner.run")]
    split = dict(trace_reduce.idle_by_host(
        dataclasses.replace(trace, host=runs), 2))
    return float(split.get("host.other", 0.0))


def analyse(ctx: dict) -> dict:
    """Everything the seven readers return, computed once a run and kept in
    ``ctx``; the hops, the counts and the idle split go to stderr."""
    if "row_journey" in ctx:
        return ctx["row_journey"]
    trace, log = ctx["trace"], ctx["log"]
    spans = program_trace.analyse(ctx)["spans"]
    out = dict.fromkeys(METRICS.values(), 0.0)
    said = (any("seq" in s[3] for s in _named(spans, "ingest.admit"))
            and any("landed" in s[3]
                    for s in _named(spans, "learner.dispatch")))
    if not said:
        log("[row_journey] the program's spans say no tickets and positions "
            "(a program older than PR 36, or a cell with no ingest): the "
            "seven metrics read 0.0")
        ctx["row_journey"] = out
        return out
    got = follow(spans, trace, ctx["chunk_program"])
    hops = hops_ms(got["rows"])
    n = got["rows"].shape[0]
    log(f"[row_journey] {got['admitted']} adds admitted in the window: "
        f"{n} followed to a chunk's end, {got['dropped']} dropped by host "
        f"staging, {got['on_the_way']} still on their way when the trace "
        f"ended; {got['pairs']} of "
        f"{got['dispatches']} dispatches paired with an execution from the "
        f"trace's end; {got['late_landed']} followed adds whose dispatch "
        "says their position had not landed")
    for key, vals in hops.items():
        if n:
            out[key] = float(np.percentile(vals, 95))
            log(f"[row_journey] {key}: median {np.median(vals):.3f} ms p95 "
                f"{out[key]:.3f} max {vals.max():.3f} min {vals.min():.3f}")
    if n:
        first3 = hops["queue"] + hops["staging"] + hops["inflight"]
        gap = np.abs(sum(hops[h] for h in HOPS) - hops["journey"]).max()
        log(f"[row_journey] admit -> commit_staged (the first three hops, "
            f"row by row): median {np.median(first3):.3f} ms p95 "
            f"{np.percentile(first3, 95):.3f}; the four hops sum to the "
            f"journey within {gap * 1e6:.3f} ns")
        # where the harness's admit_to_commit_ms.p95 ends, and the rank its
        # p95 has among the followed adds once those still on their way
        # (which wait for the profiler to stop) take the top of its sample
        hook = 1e3 * (got["hook_at"] - got["rows"][:, 0])
        hook = hook[np.isfinite(hook)]
        rank = min(100.0, 95.0 * got["admitted"] / n)
        if hook.size:
            log(f"[row_journey] admit -> the chunk hook after the commit "
                f"(learner.on_chunk; admit_to_commit_ms ends there, counted "
                f"from when the add was due): p95 "
                f"{np.percentile(hook, 95):.3f} ms, and at the "
                f"{rank:.2f}th percentile, where the p95 over all "
                f"{got['admitted']} adds falls, {np.percentile(hook, rank):.3f}"
                f"; journey at that rank "
                f"{np.percentile(hops['journey'], rank):.3f}")
        log(f"[row_journey] a chunk starts on the device "
            f"{got['dispatch_lead_s'] * 1e3:.4f} ms at the least after its "
            "dispatch began (below 0: the profiler places the device's events "
            "that much early on the host's clock)")
    per_chunk = per_chunk_host_ms(spans, trace.window)
    if per_chunk.size:
        out["ingest_host"] = float(np.median(per_chunk))
        log(f"[row_journey] ingest.commit + ingest.stage a chunk: median "
            f"{out['ingest_host']:.3f} ms p95 "
            f"{np.percentile(per_chunk, 95):.3f} max {per_chunk.max():.3f} "
            f"over {per_chunk.size} chunks")
    lo, hi = trace.window
    waits = np.asarray([1e3 * (s[2] - s[1])
                        for s in _named(spans, "ingest.lock_wait")
                        if lo <= s[1] <= hi], np.float64)
    if waits.size:
        out["lock_wait"] = float(np.percentile(waits, 95))
        log(f"[row_journey] ingest.lock_wait: median {np.median(waits):.4f} "
            f"ms p95 {out['lock_wait']:.4f} max {waits.max():.4f} over "
            f"{waits.size} spans")
    busy, window_s = trace_reduce.busy_and_window(trace)
    idle = window_s - busy
    outside = idle_outside_runs(trace, spans)
    log(f"[row_journey] idle {idle * 1e3:.3f} ms of a {window_s * 1e3:.1f} "
        f"ms window ([program_trace] splits it by innermost program span); "
        f"outside every learner.run {outside * 1e3:.3f} ms "
        f"({100 * outside / idle if idle else 0.0:.1f} % of the idle time)")
    ctx["row_journey"] = out
    return out


def read(ctx: dict, metric: str):
    """A reader's whole body: nothing without a trace, else the number."""
    if ctx.get("trace") is None:
        return None
    return float(analyse(ctx)[METRICS[metric]])
