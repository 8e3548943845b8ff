"""Programs compiled before the window that asked the persistent compile cache and
missed: ``compile.backend`` entries of the program's start-up log with a
``cache.request`` and no ``cache.hit``; their names on stderr.

0.0 on a program that keeps no start-up log (stderr says so)."""

from benchmark import startup_phases


def read(ctx):
    return startup_phases.read(ctx, "cache_miss_programs")
