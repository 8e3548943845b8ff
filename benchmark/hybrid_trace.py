"""``torso_trace`` for the LFM2 torso: its named scopes in a traced run,
reduced once a run for the readers under ``layer_metrics/`` that this file
serves (``hybrid_chunk_device_ms``, ``conv_us_per_step``,
``dense_mlp_us_per_step``, ``hybrid_attn_us_per_step``,
``hybrid_route_us_per_step``, ``hybrid_experts_us_per_step``, the three
rooflines, ``bias_swapped_share``, ``hybrid_expert_load_max_over_mean``).
The driver (``drivers/learner_static_hybrid.py``) hands the torso block over
as ``ctx["hybrid"]``.

A program without the scopes or the counter (or a run without a trace, or
another cell) gives the readers nothing to read: they return ``None``.
"""

from __future__ import annotations

import numpy as np

from benchmark import program_trace, shapes, shapes_hybrid, torso_trace

HYBRID_SCOPES = ("torso.embed", "torso.conv", "torso.attn_full", "torso.mlp",
                 "torso.route", "torso.experts", "torso.pool")
ALL_SCOPES = (program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES
              + HYBRID_SCOPES)


def analyse(ctx: dict):
    """Seconds a step under each scope (inside the scan), the chunk's
    median device time and the share the named scopes cover; ``None``
    where there is nothing to read."""
    if "hybrid_trace" in ctx:
        return ctx["hybrid_trace"]
    trace, log = ctx.get("trace"), ctx["log"]
    out = None
    if trace is not None and "hybrid" in ctx:
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
            log(f"[hybrid_trace] chunk program: {chunk['runs']} executions "
                f"of {total * 1e3:.3f} ms, K={k}; per step "
                + ", ".join(f"{s} {step[s] * 1e3:.3f} ms"
                            for s in ALL_SCOPES if step[s] > 0))
            log(f"[hybrid_trace] the named scopes cover "
                f"{100 * out['covered']:.2f} % of the chunk's device time; "
                f"in the loop under no scope "
                f"{med(chunk['loop']['']) * 1e3:.3f} ms, outside the loop "
                f"{sum(med(v) for v in chunk['outside'].values()) * 1e3:.3f}"
                f" ms, no operation running "
                f"{med(chunk['total'] - chunk['ops']) * 1e3:.3f} ms")
    ctx["hybrid_trace"] = out
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


def conv_roofline(ctx: dict):
    if "hybrid" not in ctx:
        return None
    return roofline(ctx, shapes_hybrid.conv_counts(
        ctx["hybrid"], ctx["batch_size"]),
        "short convolutions (both projections; gates and taps once)",
        "torso.conv")


def attention_roofline(ctx: dict):
    if "hybrid" not in ctx:
        return None
    return roofline(ctx, shapes_hybrid.attention_counts(
        ctx["hybrid"], ctx["batch_size"]),
        "attention (projections, products over causal pairs at 64-wide "
        "heads)", "torso.attn_full")


def experts_roofline(ctx: dict):
    if "hybrid" not in ctx or ctx.get("route_counts") is None:
        return None
    t = ctx["hybrid"]
    rows = shapes_hybrid.held_assignments(t, ctx["route_counts"])
    return roofline(ctx, shapes_hybrid.expert_counts(t, rows),
                    f"experts ({rows:.0f} held assignments a step)",
                    "torso.experts")


def swapped_share(ctx: dict):
    if ctx.get("trace") is None or ctx.get("bias_swapped") is None \
            or "hybrid" not in ctx:
        return None
    return shapes_hybrid.swapped_share(ctx["hybrid"], ctx["bias_swapped"],
                                       ctx["batch_size"])


def load_max_over_mean(ctx: dict):
    if ctx.get("trace") is None or ctx.get("route_counts") is None \
            or "hybrid" not in ctx:
        return None
    return shapes_hybrid.load_max_over_mean(ctx["hybrid"],
                                            ctx["route_counts"])
