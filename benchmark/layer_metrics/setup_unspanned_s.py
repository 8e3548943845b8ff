"""Seconds of set-up no phase of the program's start-up log names: the log's epoch
to the start of the window's first ``learner.run``, less the union of the phases
(the harness's own work, seeding and filling, is in it).

0.0 on a program that keeps no start-up log (stderr says so)."""

from benchmark import startup_phases


def read(ctx):
    return startup_phases.read(ctx, "setup_unspanned_s")
