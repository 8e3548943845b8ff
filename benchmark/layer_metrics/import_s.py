"""Seconds of set-up in first imports: the ``import.*`` phases of the program's
start-up log before the window, summed (by top-level package and self time;
stderr has them one by one).

0.0 on a program that keeps no start-up log (stderr says so)."""

from benchmark import startup_phases


def read(ctx):
    return startup_phases.read(ctx, "import_s")
