"""Distributed compute: device meshes and the sharded data-parallel learner.

The reference's only parallelism is asynchronous hogwild data parallelism
over OS shared memory (``ddpg.py:104-108``, ``shared_adam.py``,
``main.py:384-405`` — SURVEY.md §2 "Parallelism strategies"). The TPU-native
replacement is synchronous data parallelism over the ICI mesh: params and
optimizer state replicated, the batch sharded over a ``data`` axis, and the
gradient all-reduce inserted by XLA from sharding constraints (or explicit
``psum`` under ``shard_map``). A ``model`` axis is laid out from day one so
the pixel-encoder config can shard activations later (SURVEY.md §2 mandate).
"""

from d4pg_tpu.parallel.mesh import MeshSpec, make_mesh, replica_mesh
from d4pg_tpu.parallel import partition
from d4pg_tpu.parallel.data_parallel import (
    replicate_state,
    shard_batch,
    shard_stacked,
    stacked_sharding,
)

__all__ = [
    "MeshSpec",
    "make_mesh",
    "partition",
    "replica_mesh",
    "replicate_state",
    "shard_batch",
    "shard_stacked",
    "stacked_sharding",
]
