"""Seconds of set-up in backend initialisation: the ``startup.backend`` phase of
the program's start-up log (``startup.describe``'s ``jax.devices()``).

0.0 on a program that keeps no start-up log (stderr says so)."""

from benchmark import startup_phases


def read(ctx):
    return startup_phases.read(ctx, "backend_init_s")
