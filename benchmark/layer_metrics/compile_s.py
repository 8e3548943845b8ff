"""Seconds of XLA backend compilation (or cache loads) during set-up: the
sum of ``jax.monitoring`` compile-duration events before the window."""


def read(ctx):
    return ctx.get("compile_s")
