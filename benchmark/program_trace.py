"""What the program says about itself in a traced run, reduced to the numbers
the per-layer readers of PR 25 return. Read with ``jax.profiler.ProfileData``
alone, beside ``trace_reduce`` (whose ``Trace`` supplies the device's op and
program events; nothing there is changed).

Two sources, both in the program (``d4pg_tpu/obs/trace.py``):

- **named scopes.** The fused chunk and the block-commit program carry
  ``jax.named_scope``s (``replay.sample`` ... ``ingest.tree_insert``). The
  trace names a device operation by its HLO instruction, not by its scope,
  so the tie is the program table: ``compiled_text(name)`` gives the compiled
  HLO, in which every instruction carries ``metadata={op_name="jit(fn)/while/
  body/.../replay.gather/gather"}``. An event's name is the instruction's
  text on the TPU (first token ``%copy.28``) and the bare instruction name on
  the CPU client. Scope names are matched as substrings of ``op_name`` (a
  backward op reads ``transpose(jvp(update.critic))``), the innermost, i.e.
  the last one in the path, winning. Operations are split into those inside
  the scan's ``while`` body (the K steps) and those outside it (what XLA
  hoisted above the loop: the prologue).
- **host spans.** ``obs.trace.span`` writes ``learner.*`` / ``ingest.*`` /
  ``fused.*`` annotations with their stats (``block``, ``rows``, ``wait_ms``,
  ``inflight_ms``) on the profiler's clock; they are read from the run's
  ``.xplane.pb``, found as ``run.py`` finds it.

A program without the table or the spans (the parent of PR 25, over which the
driver lays these files) has nothing under these names: the device time under
a scope no instruction carries is 0.0, and a reader says so on stderr. It
cannot leave the metric out: ``manifest.validate_line`` refuses a line that
lacks a listed metric.
"""

from __future__ import annotations

import dataclasses
import os
import re
import time

import numpy as np

from benchmark import manifest, trace_reduce

CHUNK_TABLE, COMMIT_TABLE = "learner.chunk", "ingest.commit"
COMMIT_PROGRAM = "jit_commit"
TOP_SCOPES = ("replay.sample", "replay.gather", "learner.update",
              "replay.writeback")
UPDATE_SCOPES = ("update.augment", "update.critic", "update.actor",
                 "update.optim")
COMMIT_SCOPES = ("ingest.ring_write", "ingest.tree_insert")
SPAN_PREFIXES = ("learner.", "ingest.", "fused.")

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|calls|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPCODE = re.compile(r"\s(while|conditional|call)\(")


@dataclasses.dataclass
class Instruction:
    op_name: str  # the named-scope path; "" where the compiler made the op
    in_loop: bool  # inside the scan's while body (or a loop nested in it)
    container: bool  # while / conditional / call: spans its body's events


def parse_program(text: str) -> dict:
    """instruction name -> ``Instruction`` for every instruction of a
    compiled HLO module's text. "The scan's ``while``" is any ``while`` of the
    entry computation (or of a computation the entry merely ``call``s, as the
    CPU client wraps them); everything its body and condition reach is in
    the loop. An instruction the compiler made without metadata inside a
    nested ``while`` / ``conditional`` / ``call`` takes that container's
    path in front of its own: XLA expands a batched gather into a loop of
    one-row updates that carry no ``op_name``, while the loop itself keeps
    the gather's (the pixel cell's ``learner.update/vmap()/gather``)."""
    comps: dict = {}  # computation -> [(instr, op_name, opcode, called)]
    entry, current = None, None
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = _COMPUTATION.match(line)
            current = None
            if m and not line.startswith("HloModule"):
                current = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if current is None:
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        head = line.split(", metadata=", 1)[0]
        opcode = _OPCODE.search(head)
        called = _CALLED.findall(line)
        for group in _BRANCHES.findall(line):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        op_name = _OP_NAME.search(line)
        current.append((m.group(1), op_name.group(1) if op_name else "",
                        opcode.group(1) if opcode else "", called))
    if entry is None:
        raise ValueError("no ENTRY computation in the compiled text")
    top = list(comps[entry])
    for _i, _n, opcode, called in comps[entry]:
        if opcode == "call":
            for c in called:
                top += comps.get(c, [])
    reach = [c for _i, _n, opcode, called in top if opcode == "while"
             for c in called]
    loop = set()
    while reach:
        c = reach.pop()
        if c in loop or c not in comps:
            continue
        loop.add(c)
        for _i, _n, _o, called in comps[c]:
            reach += called
    # a container hands its path down to the computations it runs
    inherited = {entry: ""}
    order = [entry]
    while order:
        c = order.pop()
        for _i, op_name, opcode, called in comps[c]:
            if opcode:
                for callee in called:
                    if callee in comps and callee not in inherited:
                        inherited[callee] = inherited[c] + " " + op_name
                        order.append(callee)
    return {instr: Instruction((inherited.get(comp, "") + " " + op_name)
                               .strip(), comp in loop, bool(opcode))
            for comp, body in comps.items()
            for instr, op_name, opcode, _called in body}


def innermost(op_name: str, scopes) -> str | None:
    """The scope of ``scopes`` that comes last in the path, if any does."""
    at = {s: op_name.rfind(s) for s in scopes}
    best = max(at, key=at.get)
    return best if at[best] >= 0 else None


def instruction_of(event_name: str) -> str:
    return event_name.split(" ", 1)[0].lstrip("%")


def compiled_program(name: str, log) -> dict:
    """``parse_program`` of a program in the program's table; ``{}`` where
    the program has no table, no such entry, or cannot give its text."""
    try:
        from d4pg_tpu.obs import trace as program

        t = time.perf_counter()
        text = program.compiled_text(name)
        log(f"[program_trace] compiled text of {name!r}: {len(text)} "
            f"characters in {time.perf_counter() - t:.2f} s")
        return parse_program(text)
    except Exception as e:  # noqa: BLE001 - an older program has none
        log(f"[program_trace] no compiled text for {name!r} "
            f"({type(e).__name__}: {e}): its scopes read 0.0")
        return {}


def scope_times(trace: trace_reduce.Trace, prefix: str, program: dict,
                scopes) -> dict:
    """Device time of every execution inside the window of the program whose
    name starts with ``prefix``, split by scope: ``{"runs": n, "total": [n],
    "ops": [n] (the sum of its operations' time), "loop": {scope: [n]},
    "outside": {scope: [n]}}`` in seconds. Scope ``""`` holds the operations
    no scope of ``scopes`` names. Containers are left out (their bodies'
    operations are events of their own)."""
    start, end = trace_reduce.program_runs(trace, prefix)
    order = np.argsort(start)
    start, end = start[order], end[order]
    n = start.size
    keys = ("",) + tuple(scopes)
    out = {"runs": n, "total": end - start, "ops": np.zeros(n),
           "loop": {k: np.zeros(n) for k in keys},
           "outside": {k: np.zeros(n) for k in keys}}
    if n == 0 or not program:  # nothing to tie an operation to a scope
        return out
    run = np.searchsorted(start, trace.op_start, side="right") - 1
    ok = (run >= 0) & (trace.op_start < end[np.clip(run, 0, n - 1)])
    dur = trace.op_end - trace.op_start
    # one slot per (inside the loop or not, scope); -1 for a container. The
    # event names are few and the events many: look each name up once, then
    # sum per execution and slot with one bincount.
    slots = [(part, k) for part in ("outside", "loop") for k in keys]
    slot_of: dict = {}

    def slot(name: str) -> int:
        ins = program.get(instruction_of(name))
        if ins is None:  # not in the text: outside, under no scope
            return -1 if trace_reduce._CONTAINER.match(name) else 0
        if ins.container:
            return -1
        return slots.index(("loop" if ins.in_loop else "outside",
                            innermost(ins.op_name, scopes) or ""))

    picked = np.flatnonzero(ok)
    code = np.empty(picked.size, np.int64)
    for j, i in enumerate(picked):
        name = trace.op_names[i]
        if name not in slot_of:
            slot_of[name] = slot(name)
        code[j] = slot_of[name]
    keep = code >= 0
    picked, code = picked[keep], code[keep]
    total = np.bincount(run[picked] * len(slots) + code, weights=dur[picked],
                        minlength=n * len(slots)).reshape(n, len(slots))
    for c, (part, k) in enumerate(slots):
        out[part][k] = total[:, c]
    out["ops"] = total.sum(axis=1)
    return out


def host_spans(xplane_path: str) -> list:
    """``(name, start_s, end_s, stats)`` of the program's own annotations
    and the harness's window, from the host planes."""
    import gzip

    from jax.profiler import ProfileData

    if xplane_path.endswith(".gz"):
        with gzip.open(xplane_path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(xplane_path)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name.startswith(SPAN_PREFIXES) \
                        or name == trace_reduce.WINDOW:
                    spans.append((name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9,
                                  dict(e.stats)))
    spans.sort(key=lambda s: s[1])
    return spans


def span_stat(spans: list, name: str, stat: str,
              window: tuple) -> np.ndarray:
    """``stat`` of every ``name`` span that began inside the window."""
    lo, hi = window
    return np.asarray([float(s[3][stat]) for s in spans
                       if s[0] == name and lo <= s[1] <= hi
                       and stat in s[3]], np.float64)


def idle_by_span(trace: trace_reduce.Trace, spans: list, n: int = 8) -> list:
    """Idle time inside the window by the innermost *program* span: the
    ``n`` largest owners."""
    own = [s[:3] for s in spans if s[0] != trace_reduce.WINDOW]
    return trace_reduce.idle_by_host(dataclasses.replace(trace, host=own), n)


def _us(x: float) -> str:
    return f"{x * 1e6:.1f} us"


def analyse(ctx: dict) -> dict:
    """Everything the nine readers return, computed once a run and kept in
    ``ctx``; the breakdown goes to stderr. ``ctx["xplane_path"]`` names the
    trace file where it is not the run's newest (the tests' fixture)."""
    if "program_trace" in ctx:
        return ctx["program_trace"]
    trace, log, k = ctx["trace"], ctx["log"], int(ctx["k"])
    # the trace file first, while it is surely there: the next run of any
    # cell in this checkout empties the directory
    spans = host_spans(ctx.get("xplane_path") or trace_reduce.newest_xplane(
        os.path.join(manifest.REPO, ".bench_trace")))
    chunk_text = (ctx["chunk_text"] if "chunk_text" in ctx
                  else compiled_program(CHUNK_TABLE, log))
    chunk = scope_times(trace, ctx["chunk_program"], chunk_text,
                        TOP_SCOPES + UPDATE_SCOPES)
    med = lambda a: float(np.median(a)) if a.size else 0.0  # noqa: E731
    out: dict = {}
    # a child's time is its parent's too: learner.update is its own
    # operations and those of its three children
    loop = dict(chunk["loop"])
    loop["learner.update"] = loop["learner.update"] + sum(
        loop[c] for c in UPDATE_SCOPES)
    for scope in TOP_SCOPES:
        out[scope] = med(loop[scope]) / k
    outside = sum(chunk["outside"].values())
    out["prologue"] = med(outside)
    # the remainder, for whoever adds the five up: operations of the loop
    # under no scope, and time in which no operation of the program ran
    out["loop_unscoped"] = med(loop[""])
    out["not_running"] = med(chunk["total"] - chunk["ops"])
    out["chunk_total"] = med(chunk["total"])
    if chunk["runs"]:
        total = med(chunk["total"])
        named = k * sum(out[s] for s in TOP_SCOPES) + out["prologue"]
        log(f"[program_trace] chunk program: {chunk['runs']} executions of "
            f"{total * 1e3:.3f} ms, K={k}; per step "
            + ", ".join(f"{s} {_us(out[s])}" for s in TOP_SCOPES)
            + "; learner.update = "
            + ", ".join(f"{c} {_us(med(loop[c]) / k)}"
                        for c in UPDATE_SCOPES)
            + f" + {_us(med(chunk['loop']['learner.update']) / k)} its own")
        log(f"[program_trace] outside the loop {out['prologue'] * 1e3:.3f} "
            "ms an execution: "
            + ", ".join(f"{s or 'no scope'} {med(v) * 1e3:.3f} ms"
                        for s, v in chunk["outside"].items() if med(v) > 0))
        log(f"[program_trace] K x the four scopes + prologue = "
            f"{named * 1e3:.3f} ms of {total * 1e3:.3f} ms "
            f"({100 * named / total:.2f} %); unattributed "
            f"{(total - named) * 1e3:.3f} ms: in the loop under no scope "
            f"{out['loop_unscoped'] * 1e3:.3f} ms, no operation running "
            f"{out['not_running'] * 1e3:.3f} ms")
    # the commit program
    commit_text = (ctx["commit_text"] if "commit_text" in ctx
                   else compiled_program(COMMIT_TABLE, log) if _ran(trace)
                   else {})
    commit = scope_times(trace, COMMIT_PROGRAM, commit_text, COMMIT_SCOPES)
    out["commit"] = med(commit["total"])
    out["commit_runs"] = commit["runs"]
    if commit["runs"]:
        both = {s: commit["loop"][s] + commit["outside"][s]
                for s in ("",) + COMMIT_SCOPES}
        log(f"[program_trace] commit program: {commit['runs']} executions, "
            f"median {out['commit'] * 1e3:.3f} ms: "
            + ", ".join(f"{s or 'no scope'} {med(v) * 1e3:.3f} ms"
                        for s, v in both.items()))
    # the program's host spans
    out["spans"] = spans
    for key, name, stat in (("staging_wait", "fused.stage_block", "wait_ms"),
                            ("inflight", "fused.commit_staged",
                             "inflight_ms")):
        vals = span_stat(spans, name, stat, trace.window)
        out[key] = med(vals)
        if vals.size:
            log(f"[program_trace] {name} {stat}: median {med(vals):.3f} "
                f"p95 {np.percentile(vals, 95):.3f} max {vals.max():.3f} "
                f"over {vals.size} spans")
        elif _ran(trace):
            log(f"[program_trace] no {name} span in the window: "
                f"{stat} reads 0.0")
    own = sorted({s[0] for s in spans})
    log(f"[program_trace] program spans in the trace: {own}")
    idle = idle_by_span(trace, spans)
    log(f"[program_trace] idle time by innermost program span: {idle}")
    s, e = trace_reduce.program_runs(trace, ctx["chunk_program"])
    if s.size > 1 and commit["runs"]:
        gap = float(np.median(s[1:] - e[:-1]))
        idle_s = sum(v for _n, v in idle)
        log(f"[program_trace] launch gap {gap * 1e3:.3f} ms = commit "
            f"program {out['commit'] * 1e3:.3f} ms + "
            f"{(gap - out['commit']) * 1e3:.3f} ms else; idle "
            f"{idle_s / s.size * 1e3:.3f} ms a chunk over the window")
    ctx["program_trace"] = out
    return out


def _ran(trace: trace_reduce.Trace) -> bool:
    """Whether the commit program ran at all (a static cell never builds
    it, and asking the table for it would only log a miss)."""
    return any(n.startswith(COMMIT_PROGRAM) for n in trace.mod_names)


def read_scope(ctx: dict, key: str, scale: float):
    """A reader's whole body: nothing without a trace, else the number."""
    if ctx.get("trace") is None:
        return None
    return float(analyse(ctx)[key] * scale)
