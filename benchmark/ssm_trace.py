"""``torso_trace`` for the Nemotron-H torso: its named scopes in a traced run,
reduced once a run for the readers under ``layer_metrics/`` that this file
serves (``ssm_chunk_device_ms``, ``mamba_us_per_step``,
``ssd_scan_us_per_step``, ``ssm_attn_us_per_step``,
``ssm_shared_expert_us_per_step``, ``ssm_route_us_per_step``,
``ssm_experts_us_per_step``, the four rooflines, ``ssm_step_mfu``,
``ssd_kept_share``, ``ssm_bias_swapped_share``,
``ssm_expert_load_max_over_mean``). The driver
(``drivers/learner_static_ssm.py``) hands the torso block over as
``ctx["ssm"]`` and the last chunk's counters beside it.

A program without the scopes or the counters (or a run without a trace, or
another cell) gives the readers nothing to read: they return ``None``.
"""

from __future__ import annotations

import numpy as np

from benchmark import program_trace, shapes, shapes_ssm, torso_trace

SSM_SCOPES = ("torso.embed", "torso.mamba", "torso.ssd_scan",
              "torso.attn_full", "torso.shared_expert", "torso.route",
              "torso.experts", "torso.pool")
ALL_SCOPES = (program_trace.TOP_SCOPES + program_trace.UPDATE_SCOPES
              + SSM_SCOPES)


def analyse(ctx: dict):
    """Seconds a step under each scope (inside the scan), the chunk's
    median device time and the share the named scopes cover; ``None``
    where there is nothing to read."""
    if "ssm_trace" in ctx:
        return ctx["ssm_trace"]
    trace, log = ctx.get("trace"), ctx["log"]
    out = None
    if trace is not None and "ssm" in ctx:
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
            log(f"[ssm_trace] chunk program: {chunk['runs']} executions "
                f"of {total * 1e3:.3f} ms, K={k}; per step "
                + ", ".join(f"{s} {step[s] * 1e3:.3f} ms"
                            for s in ALL_SCOPES if step[s] > 0))
            log(f"[ssm_trace] the named scopes cover "
                f"{100 * out['covered']:.2f} % of the chunk's device time; "
                f"in the loop under no scope "
                f"{med(chunk['loop']['']) * 1e3:.3f} ms, outside the loop "
                f"{sum(med(v) for v in chunk['outside'].values()) * 1e3:.3f}"
                f" ms, no operation running "
                f"{med(chunk['total'] - chunk['ops']) * 1e3:.3f} ms")
    ctx["ssm_trace"] = out
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


def _by_size(ctx: dict, counts, what: str, scope: str):
    """``roofline`` of ``counts(torso block, batch)``, sizes alone."""
    if "ssm" not in ctx:
        return None
    return roofline(ctx, counts(ctx["ssm"], ctx["batch_size"]), what, scope)


def mamba_roofline(ctx: dict):
    return _by_size(
        ctx, shapes_ssm.mamba_counts,
        "Mamba-2 mixers without the recurrence (two projections; z, xBC, y "
        "written and read once)", "torso.mamba")


def ssd_scan_roofline(ctx: dict):
    return _by_size(
        ctx, shapes_ssm.ssd_scan_counts,
        "the recurrence token by token (decay, write and read of a [P, N] "
        "state a head and token)", "torso.ssd_scan")


def attention_roofline(ctx: dict):
    return _by_size(
        ctx, shapes_ssm.attention_counts,
        "attention without rotary embedding (projections, products over "
        "causal pairs at 32 heads of 128)", "torso.attn_full")


def experts_roofline(ctx: dict):
    if "ssm" not in ctx or ctx.get("route_counts") is None:
        return None
    t = ctx["ssm"]
    rows = shapes_ssm.held_assignments(t, ctx["route_counts"])
    return roofline(ctx, shapes_ssm.expert_counts(t, rows),
                    f"relu2 experts ({rows:.0f} held assignments a step, "
                    f"two matrices each)", "torso.experts")


def step_mfu(ctx: dict):
    """Percent of the chip's bfloat16 peak that the step's needed model
    FLOPs reach over the whole chunk's device time a step. No clamp."""
    found = analyse(ctx)
    if found is None or ctx.get("peak") is None or "ssm" not in ctx \
            or ctx.get("route_counts") is None:
        return None
    flops = shapes_ssm.step_flops(ctx["ssm"], ctx["batch_size"],
                                  ctx["route_counts"])
    spent = found["total"] / int(ctx["k"])
    ctx["log"](f"[roofline] whole step: {flops:.4g} FLOP needed, "
               f"{flops / ctx['peak']['bf16_flops_per_s'] * 1e3:.3f} ms at "
               f"peak, {spent * 1e3:.3f} ms of device time a step")
    return float(100.0 * flops / ctx["peak"]["bf16_flops_per_s"] / spent)


def _counter(ctx: dict, name: str):
    if ctx.get("trace") is None or ctx.get(name) is None \
            or "ssm" not in ctx:
        return None
    return ctx[name]


def kept_share(ctx: dict):
    kept = _counter(ctx, "ssd_kept")
    return None if kept is None else shapes_ssm.kept_share(kept)


def swapped_share(ctx: dict):
    swapped = _counter(ctx, "bias_swapped")
    return None if swapped is None else shapes_ssm.swapped_share(
        ctx["ssm"], swapped, ctx["batch_size"])


def load_max_over_mean(ctx: dict):
    counts = _counter(ctx, "route_counts")
    return None if counts is None else shapes_ssm.load_max_over_mean(
        ctx["ssm"], counts)
