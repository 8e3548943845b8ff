"""Seconds of set-up spent reading executables from the persistent compile cache:
the ``cache.load`` entries of the program's start-up log before the window
(inside other phases: not additive).

0.0 on a program that keeps no start-up log (stderr says so)."""

from benchmark import startup_phases


def read(ctx):
    return startup_phases.read(ctx, "cache_load_s")
