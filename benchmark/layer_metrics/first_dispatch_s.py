"""Seconds of set-up in the first call of each registered program (trace, lower,
compile or load, dispatch): the ``learner.first_dispatch`` phases of the
program's start-up log, ``learner.chunk`` and, in the ingest cell,
``ingest.commit``; stderr splits each into trace + lower + backend + self.

0.0 on a program that keeps no start-up log (stderr says so)."""

from benchmark import startup_phases


def read(ctx):
    return startup_phases.read(ctx, "first_dispatch_s")
