"""``torso_trace`` for the Qwen3-Next torso: its named scopes in a traced
run, reduced once a run for the readers under ``layer_metrics/`` that this
file serves (``linear_chunk_device_ms``, ``deltanet_us_per_step``,
``delta_scan_us_per_step``, ``gated_attn_us_per_step``,
``shared_expert_us_per_step``, ``linear_route_us_per_step``,
``linear_experts_us_per_step``, the four rooflines, ``delta_kept_share``,
``linear_expert_load_max_over_mean``). The driver
(``drivers/learner_static_linear.py``) hands the torso block over as
``ctx["linear"]``.

A program without the scopes or the counters (or a run without a trace, or
another cell) gives the readers nothing to read: they return ``None``.
"""

from __future__ import annotations

import numpy as np

from benchmark import program_trace, shapes, shapes_linear, torso_trace

LINEAR_SCOPES = ("torso.embed", "torso.deltanet", "torso.delta_scan",
                 "torso.attn_full", "torso.shared_expert", "torso.route",
                 "torso.experts", "torso.pool")
ALL_SCOPES = (program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES
              + LINEAR_SCOPES)


def analyse(ctx: dict):
    """Seconds a step under each scope (inside the scan), the chunk's
    median device time and the share the named scopes cover; ``None``
    where there is nothing to read."""
    if "linear_trace" in ctx:
        return ctx["linear_trace"]
    trace, log = ctx.get("trace"), ctx["log"]
    out = None
    if trace is not None and "linear" in ctx:
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
            log(f"[linear_trace] chunk program: {chunk['runs']} executions "
                f"of {total * 1e3:.3f} ms, K={k}; per step "
                + ", ".join(f"{s} {step[s] * 1e3:.3f} ms"
                            for s in ALL_SCOPES if step[s] > 0))
            log(f"[linear_trace] the named scopes cover "
                f"{100 * out['covered']:.2f} % of the chunk's device time; "
                f"in the loop under no scope "
                f"{med(chunk['loop']['']) * 1e3:.3f} ms, outside the loop "
                f"{sum(med(v) for v in chunk['outside'].values()) * 1e3:.3f}"
                f" ms, no operation running "
                f"{med(chunk['total'] - chunk['ops']) * 1e3:.3f} ms")
    ctx["linear_trace"] = out
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


def deltanet_roofline(ctx: dict):
    if "linear" not in ctx:
        return None
    return roofline(ctx, shapes_linear.deltanet_counts(
        ctx["linear"], ctx["batch_size"]),
        "Gated DeltaNet operators without the recurrence (three "
        "projections; q, k, v, z written and read once)", "torso.deltanet")


def delta_scan_roofline(ctx: dict):
    if "linear" not in ctx:
        return None
    return roofline(ctx, shapes_linear.delta_scan_counts(
        ctx["linear"], ctx["batch_size"]),
        "the recurrence token by token (three [Dk, Dv] products a value "
        "head and token)", "torso.delta_scan")


def attention_roofline(ctx: dict):
    if "linear" not in ctx:
        return None
    return roofline(ctx, shapes_linear.attention_counts(
        ctx["linear"], ctx["batch_size"]),
        "gated attention (projections, products over causal pairs at "
        "256-wide heads)", "torso.attn_full")


def experts_roofline(ctx: dict):
    if "linear" not in ctx or ctx.get("route_counts") is None:
        return None
    t = ctx["linear"]
    rows = shapes_linear.held_assignments(t, ctx["route_counts"])
    return roofline(ctx, shapes_linear.expert_counts(t, rows),
                    f"experts ({rows:.0f} held assignments a step)",
                    "torso.experts")


def kept_share(ctx: dict):
    if ctx.get("trace") is None or ctx.get("delta_kept") is None \
            or "linear" not in ctx:
        return None
    return shapes_linear.kept_share(ctx["delta_kept"])


def load_max_over_mean(ctx: dict):
    if ctx.get("trace") is None or ctx.get("route_counts") is None \
            or "linear" not in ctx:
        return None
    return shapes_linear.load_max_over_mean(ctx["linear"],
                                            ctx["route_counts"])
