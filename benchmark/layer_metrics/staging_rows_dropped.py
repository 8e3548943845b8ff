"""Rows the host staging rings discarded (``fused.rows_dropped`` in the
program's registry) by the end of the run. 0.0 is the value to expect."""


def read(ctx):
    if ctx.get("trace") is None:
        return None
    from d4pg_tpu.obs.registry import REGISTRY

    return float(REGISTRY.counter("fused.rows_dropped").value)
