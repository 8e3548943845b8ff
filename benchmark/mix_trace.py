"""``torso_trace`` for the Trinity-Mini torso: its named scopes in a traced
run, reduced once a run for the readers under ``layer_metrics/`` that this
file serves (``mix_chunk_device_ms``, ``mix_attn_window_us_per_step``,
``mix_attn_full_us_per_step``, ``mix_dense_mlp_us_per_step``,
``mix_route_us_per_step``, ``mix_experts_us_per_step``,
``mix_shared_expert_us_per_step``, the three rooflines, ``mix_step_mfu``,
``mix_expert_load_max_over_mean``, ``mix_bias_swapped_share``). The driver
(``drivers/learner_static_mix.py``) hands the torso block over as
``ctx["mix"]`` and the last chunk's counters beside it.

A program without the scopes or the counters (or a run without a trace, or
another cell) gives the readers nothing to read: they return ``None``.
"""

from __future__ import annotations

import numpy as np

from benchmark import program_trace, shapes, shapes_mix, torso_trace

MIX_SCOPES = ("torso.embed", "torso.attn_window", "torso.attn_full",
              "torso.mlp", "torso.route", "torso.experts",
              "torso.shared_expert", "torso.pool")
ALL_SCOPES = (program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES
              + MIX_SCOPES)


def analyse(ctx: dict):
    """Seconds a step under each scope (inside the scan), the chunk's
    median device time and the share the named scopes cover; ``None``
    where there is nothing to read."""
    if "mix_trace" in ctx:
        return ctx["mix_trace"]
    trace, log = ctx.get("trace"), ctx["log"]
    out = None
    if trace is not None and "mix" in ctx:
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
            log(f"[mix_trace] chunk program: {chunk['runs']} executions "
                f"of {total * 1e3:.3f} ms, K={k}; per step "
                + ", ".join(f"{s} {step[s] * 1e3:.3f} ms"
                            for s in ALL_SCOPES if step[s] > 0))
            log(f"[mix_trace] the named scopes cover "
                f"{100 * out['covered']:.2f} % of the chunk's device time; "
                f"in the loop under no scope "
                f"{med(chunk['loop']['']) * 1e3:.3f} ms, outside the loop "
                f"{sum(med(v) for v in chunk['outside'].values()) * 1e3:.3f}"
                f" ms (" + ", ".join(
                    f"{s or 'under no scope'} {med(v) * 1e3:.3f}"
                    for s, v in chunk["outside"].items() if med(v) > 0)
                + "), no operation running "
                f"{med(chunk['total'] - chunk['ops']) * 1e3:.3f} ms")
    ctx["mix_trace"] = out
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


def attention_roofline(ctx: dict, kind: str):
    """``kind``: ``sliding_attention`` (scope ``torso.attn_window``) or
    ``full_attention`` (``torso.attn_full``)."""
    if "mix" not in ctx:
        return None
    t = ctx["mix"]
    window = kind == "sliding_attention"
    return roofline(
        ctx, shapes_mix.attention_counts(t, ctx["batch_size"], kind),
        f"{shapes_mix.layers(t, kind)} gated attention layer(s), "
        + (f"window {t['sliding_window']} with rotary embedding" if window
           else "full without rotary embedding")
        + " (five projections, products over the pairs the mask keeps)",
        "torso.attn_window" if window else "torso.attn_full")


def experts_roofline(ctx: dict):
    if "mix" not in ctx or ctx.get("route_counts") is None:
        return None
    t = ctx["mix"]
    rows = shapes_mix.held_assignments(t, ctx["route_counts"])
    return roofline(ctx, shapes_mix.expert_counts(t, rows),
                    f"experts ({rows:.0f} held assignments a step, three "
                    f"matrices each)", "torso.experts")


def step_mfu(ctx: dict):
    """Percent of the chip's bfloat16 peak that the step's needed model
    FLOPs reach over the whole chunk's device time a step. No clamp."""
    found = analyse(ctx)
    if found is None or ctx.get("peak") is None or "mix" not in ctx \
            or ctx.get("route_counts") is None:
        return None
    flops = shapes_mix.step_flops(ctx["mix"], ctx["batch_size"],
                                  ctx["route_counts"])
    spent = found["total"] / int(ctx["k"])
    ctx["log"](f"[roofline] whole step: {flops:.4g} FLOP needed, "
               f"{flops / ctx['peak']['bf16_flops_per_s'] * 1e3:.3f} ms at "
               f"peak, {spent * 1e3:.3f} ms of device time a step")
    return float(100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / spent)


def _counter(ctx: dict, name: str):
    if ctx.get("trace") is None or ctx.get(name) is None \
            or "mix" not in ctx:
        return None
    return ctx[name]


def swapped_share(ctx: dict):
    swapped = _counter(ctx, "bias_swapped")
    return None if swapped is None else shapes_mix.swapped_share(
        ctx["mix"], swapped, ctx["batch_size"])


def load_max_over_mean(ctx: dict):
    counts = _counter(ctx, "route_counts")
    return None if counts is None else shapes_mix.load_max_over_mean(
        ctx["mix"], counts)
