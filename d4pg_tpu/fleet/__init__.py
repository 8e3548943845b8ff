"""Fleet plane: fan-out stress harness with seeded fault injection.

The distributed layer (``d4pg_tpu/distributed``) gives one actor a
correct transport; this package answers what happens when there are 256
of them and the network is having a bad day. ``FleetHarness`` runs N
throttled sender lanes against one ``ReplayService`` receiver over real
TCP, a seeded ``ChaosPolicy`` injects drops/delays/crashes/receiver
stalls at the transport boundary, and the harness reports what survived:
rows/s, latency percentiles, every counted loss, and recovery times.
``sweep.run_sweep`` walks N ∈ {8..256} and emits the ``bench_fleet``
artifact (``python -m d4pg_tpu.fleet.sweep``). See docs/architecture.md
"Fleet plane".
"""

from d4pg_tpu.fleet.chaos import (
    ActorChaos,
    ChaosConfig,
    ChaosEvent,
    ChaosPolicy,
    StallGate,
)
from d4pg_tpu.fleet.harness import FleetConfig, FleetHarness
from d4pg_tpu.fleet.sender import ThrottledSender, synthetic_block
from d4pg_tpu.fleet.sweep import (
    SWEEP_NS,
    default_chaos,
    run_sweep,
    shard_sweep,
)

__all__ = [
    "ActorChaos",
    "ChaosConfig",
    "ChaosEvent",
    "ChaosPolicy",
    "StallGate",
    "FleetConfig",
    "FleetHarness",
    "ThrottledSender",
    "synthetic_block",
    "SWEEP_NS",
    "default_chaos",
    "run_sweep",
    "shard_sweep",
]
