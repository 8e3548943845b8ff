"""Seconds of set-up spent tracing and lowering, which no compile cache saves: the
union of the ``compile.trace`` and ``compile.lower`` intervals the program's
start-up log holds before the window (inside other phases: not additive).

0.0 on a program that keeps no start-up log (stderr says so)."""

from benchmark import startup_phases


def read(ctx):
    return startup_phases.read(ctx, "trace_lower_s")
