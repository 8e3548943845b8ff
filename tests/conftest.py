"""Test configuration: force the CPU backend with 8 virtual XLA devices.

Per SURVEY.md §4, multi-host/multi-chip behavior is tested on a simulated
8-device CPU mesh (the driver separately dry-runs the multichip path).

The suite pins the CPU itself (``jax.config.update`` before the backend
initializes), so it runs the same with or without ``JAX_PLATFORMS=cpu`` in
the environment; ``XLA_FLAGS`` only needs to be set before the first
backend-initializing jax call. It never calls ``d4pg_tpu.startup``, so a
tier-1 run writes nothing into the in-checkout compile cache.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
