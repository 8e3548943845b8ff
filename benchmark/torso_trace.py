"""The torso's named scopes in a traced run, reduced once a run for the
readers under ``layer_metrics/`` that this file serves
(``torso_chunk_device_ms``, ``attn_*_us_per_step``, ``experts_us_per_step``,
``route_us_per_step``, ``attn_roofline``, ``experts_roofline``,
``expert_load_max_over_mean``). Built on ``program_trace``: the chunk
program's compiled text ties each device operation to its innermost scope.

A program without the scopes (or a run without a trace) gives the readers
nothing to read: they return ``None``.
"""

from __future__ import annotations

import numpy as np

from benchmark import program_trace, shapes, shapes_torso

TORSO_SCOPES = ("torso.embed", "torso.attn_window", "torso.attn_full",
                "torso.route", "torso.experts", "torso.pool")
ALL_SCOPES = (program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES
              + TORSO_SCOPES)


def one_line_each(text: str) -> str:
    """The compiled text with every instruction on one line. A Pallas
    kernel's ``frontend_attributes`` (the splash kernel's
    ``kernel_metadata``) come back over several lines, the continuations
    at column 0, where ``program_trace.parse_program`` reads a line at
    column 0 as the end of a computation and loses the instructions after
    it. A continuation starts with a quote or with a brace that something
    follows."""
    out = []
    for line in text.splitlines():
        if out and (line.startswith('"') or (
                line.startswith("}") and line.strip() != "}")):
            out[-1] += " " + line
        else:
            out.append(line)
    return "\n".join(out)


def under_experts(program: dict) -> dict:
    """XLA's own grouped product (what ``jax.lax.ragged_dot`` becomes on
    the TPU: ``ragged-dot*`` custom calls) comes out of the compiler
    without the ``op_name`` its ``dot`` had, so it would be read under its
    loop's path; nothing but the expert products makes one, so it is read
    under ``torso.experts``."""
    for name, ins in program.items():
        if name.startswith("ragged-dot") and "torso." not in ins.op_name:
            ins.op_name += " torso.experts"
    return program


def chunk_program(log) -> dict:
    """``program_trace.compiled_program`` of the chunk program, read from
    ``one_line_each`` of its text; ``{}`` where the program has no table
    or cannot give its text."""
    try:
        from d4pg_tpu.obs import trace as program

        return under_experts(program_trace.parse_program(one_line_each(
            program.compiled_text(program_trace.CHUNK_TABLE))))
    except Exception as e:  # noqa: BLE001 - an older program has none
        log(f"[torso_trace] no compiled text for the chunk program "
            f"({type(e).__name__}: {e})")
        return {}


def analyse(ctx: dict):
    """Seconds a step under each scope (inside the scan), the chunk's
    median device time and the share the named scopes cover; ``None``
    where there is nothing to read."""
    if "torso_trace" in ctx:
        return ctx["torso_trace"]
    trace, log = ctx.get("trace"), ctx["log"]
    out = None
    if trace is not None and "torso" in ctx:
        k = int(ctx["k"])
        text = (ctx["chunk_text"] if "chunk_text" in ctx
                else chunk_program(log))
        chunk = program_trace.scope_times(trace, ctx["chunk_program"], text,
                                          ALL_SCOPES)
        if chunk["runs"] and text:
            med = lambda a: float(np.median(a))  # noqa: E731
            total = med(chunk["total"])
            step = {s: med(chunk["loop"][s]) / k for s in ALL_SCOPES}
            named = sum(med(chunk["loop"][s]) + med(chunk["outside"][s])
                        for s in ALL_SCOPES)
            out = {"total": total, "step": step, "covered": named / total}
            log(f"[torso_trace] chunk program: {chunk['runs']} executions "
                f"of {total * 1e3:.3f} ms, K={k}; per step "
                + ", ".join(f"{s} {step[s] * 1e3:.3f} ms"
                            for s in ALL_SCOPES if step[s] > 0))
            log(f"[torso_trace] the named scopes cover "
                f"{100 * out['covered']:.2f} % of the chunk's device time; "
                f"in the loop under no scope "
                f"{med(chunk['loop']['']) * 1e3:.3f} ms, outside the loop "
                f"{sum(med(v) for v in chunk['outside'].values()) * 1e3:.3f}"
                f" ms, no operation running "
                f"{med(chunk['total'] - chunk['ops']) * 1e3:.3f} ms")
    ctx["torso_trace"] = out
    return out


def scope_us(ctx: dict, *scopes):
    found = analyse(ctx)
    if found is None:
        return None
    return float(1e6 * sum(found["step"][s] for s in scopes))


def roofline(ctx: dict, counts: dict, what: str, *scopes):
    """Percent: the least time the chip could take for ``counts`` over the
    time a step spends under ``scopes``. No clamp."""
    found = analyse(ctx)
    if found is None or ctx.get("peak") is None:
        return None
    spent = sum(found["step"][s] for s in scopes)
    least, bound = shapes.roofline_seconds(counts, ctx["peak"])
    ctx["log"](f"[roofline] {what}: a step needs {counts['flops']:.4g} FLOP "
               f"and {counts['bytes']:.4g} B: bound by {bound}, "
               f"{least * 1e3:.3f} ms at peak, {spent * 1e3:.3f} ms spent")
    # a scope no operation carries reads 0.0, as the time metrics do
    return float(100.0 * least / spent) if spent > 0 else 0.0


def attn_roofline(ctx: dict):
    if "torso" not in ctx:
        return None
    return roofline(ctx, shapes_torso.attention_counts(
        ctx["torso"], ctx["batch_size"]), "attention layers",
        "torso.attn_window", "torso.attn_full")


def experts_roofline(ctx: dict):
    if "torso" not in ctx or ctx.get("route_counts") is None:
        return None
    t = ctx["torso"]
    rows = shapes_torso.held_assignments(t, ctx["route_counts"])
    return roofline(ctx, shapes_torso.expert_counts(t, rows),
                    f"experts ({rows:.0f} held assignments a step)",
                    "torso.experts")
