"""Device time a gradient step spends under the chunk program's
``learner.update`` scope (``update_step``: its children ``update.critic``,
``update.actor``, ``update.optim`` go to stderr), inside the scan: the median
over chunk executions of the scope's time over K."""

from benchmark import program_trace


def read(ctx):
    return program_trace.read_scope(ctx, "learner.update", 1e6)
