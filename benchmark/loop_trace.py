"""``torso_trace`` for the looped Ouro torso: its named scopes in a traced
run, reduced once a run for the readers under ``layer_metrics/`` that this
file serves (``loop_chunk_device_ms``, ``loop_attn_us_per_step``,
``loop_mlp_us_per_step``, ``loop_exit_us_per_step``, ``loop_attn_roofline``,
``loop_mlp_roofline``, ``loop_step_mfu``, ``exit_last_share``,
``exit_entropy_share``). The driver (``drivers/learner_static_loop.py``)
hands the torso block over as ``ctx["loop"]`` and the last chunk's
``exit_dist [K, R]`` beside it.

A program without the scopes or the counter (or a run without a trace, or
another cell) gives the readers nothing to read: they return ``None``.
"""

from __future__ import annotations

import numpy as np

from benchmark import program_trace, shapes, shapes_loop, torso_trace

LOOP_SCOPES = ("torso.embed", "torso.attn_full", "torso.mlp", "torso.exit")
ALL_SCOPES = (program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES
              + LOOP_SCOPES)


def analyse(ctx: dict):
    """Seconds a step under each scope (inside the scan), the chunk's
    median device time and the share the named scopes cover; ``None``
    where there is nothing to read."""
    if "loop_trace" in ctx:
        return ctx["loop_trace"]
    trace, log = ctx.get("trace"), ctx["log"]
    out = None
    if trace is not None and "loop" in ctx:
        k = int(ctx["k"])
        text = (ctx["chunk_text"] if "chunk_text" in ctx
                else torso_trace.chunk_program(log))
        chunk = program_trace.scope_times(trace, ctx["chunk_program"], text,
                                          ALL_SCOPES)
        if chunk["runs"] and text:
            med = lambda a: float(np.median(a))  # noqa: E731
            total = med(chunk["total"])
            step = {s: med(chunk["loop"][s]) / k for s in ALL_SCOPES}
            named = sum(med(chunk["loop"][s]) + med(chunk["outside"][s])
                        for s in ALL_SCOPES)
            out = {"total": total, "step": step, "covered": named / total}
            log(f"[loop_trace] chunk program: {chunk['runs']} executions "
                f"of {total * 1e3:.3f} ms, K={k}; per step "
                + ", ".join(f"{s} {step[s] * 1e3:.3f} ms"
                            for s in ALL_SCOPES if step[s] > 0))
            log(f"[loop_trace] the named scopes cover "
                f"{100 * out['covered']:.2f} % of the chunk's device time; "
                f"in the loop under no scope "
                f"{med(chunk['loop']['']) * 1e3:.3f} ms, outside the loop "
                f"{sum(med(v) for v in chunk['outside'].values()) * 1e3:.3f}"
                f" ms, no operation running "
                f"{med(chunk['total'] - chunk['ops']) * 1e3:.3f} ms")
    ctx["loop_trace"] = out
    return out


def chunk_ms(ctx: dict):
    found = analyse(ctx)
    return None if found is None else float(found["total"] * 1e3)


def scope_us(ctx: dict, scope: str):
    found = analyse(ctx)
    return None if found is None else float(1e6 * found["step"][scope])


def roofline(ctx: dict, counts: dict, what: str, scope: str):
    """Percent: the least time the chip could take for ``counts`` over the
    time a step spends under ``scope``. No clamp."""
    found = analyse(ctx)
    if found is None or ctx.get("peak") is None:
        return None
    spent = found["step"][scope]
    least, bound = shapes.roofline_seconds(counts, ctx["peak"])
    ctx["log"](f"[roofline] {what}: a step needs {counts['flops']:.4g} FLOP "
               f"and {counts['bytes']:.4g} B: bound by {bound}, "
               f"{least * 1e3:.3f} ms at peak, {spent * 1e3:.3f} ms spent")
    # a scope no operation carries reads 0.0, as the time metrics do
    return float(100.0 * least / spent) if spent > 0 else 0.0


def attention_roofline(ctx: dict):
    if "loop" not in ctx:
        return None
    t = ctx["loop"]
    return roofline(ctx, shapes_loop.attention_counts(t, ctx["batch_size"]),
                    f"attention ({shapes_loop.applications(t)} applications "
                    f"a pass: projections, products over causal pairs)",
                    "torso.attn_full")


def mlp_roofline(ctx: dict):
    if "loop" not in ctx:
        return None
    t = ctx["loop"]
    return roofline(ctx, shapes_loop.mlp_counts(t, ctx["batch_size"]),
                    f"SwiGLU ({shapes_loop.applications(t)} applications a "
                    f"pass: three products)", "torso.mlp")


def step_mfu(ctx: dict):
    """Percent of the chip's bfloat16 peak that the step's needed model
    FLOPs reach over the whole chunk's device time a step. No clamp."""
    found = analyse(ctx)
    if found is None or ctx.get("peak") is None or "loop" not in ctx:
        return None
    flops = shapes_loop.step_flops(ctx["loop"], ctx["batch_size"])
    spent = found["total"] / int(ctx["k"])
    ctx["log"](f"[roofline] whole step: {flops:.4g} FLOP needed, "
               f"{flops / ctx['peak']['bf16_flops_per_s'] * 1e3:.3f} ms at "
               f"peak, {spent * 1e3:.3f} ms of device time a step")
    return float(100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / spent)


def _exit_dist(ctx: dict):
    if ctx.get("trace") is None or ctx.get("exit_dist") is None \
            or "loop" not in ctx:
        return None
    return ctx["exit_dist"]


def exit_last_share(ctx: dict):
    dist = _exit_dist(ctx)
    return None if dist is None else shapes_loop.exit_last_share(dist)


def exit_entropy_share(ctx: dict):
    dist = _exit_dist(ctx)
    return None if dist is None else shapes_loop.exit_entropy_share(dist)
