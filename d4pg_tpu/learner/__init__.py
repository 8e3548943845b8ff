"""Learner core: train state, the one D4PG update step and its builders.

The reference's hot loop (``ddpg.py:200-255``, call stack SURVEY.md S2) spans
torch autograd, a host-side numpy projection round-trip, shared-memory
optimizers and python parameter loops. Here the entire update — target
forward, Bellman projection, both losses, gradients, Adam, soft target
update, TD-error outputs for PER — is ONE pure function
(``update.update_step``) that three builders jit: ``make_update`` (a step a
dispatch), ``make_multi_update`` (K scanned steps on host-sampled batches)
and ``make_fused_chunk`` (K scanned steps that also sample the ring and
write the priorities back on the device: the path every benchmark cell
runs, ``PERF.md`` section 3). Each takes ``mesh=`` for data parallelism.
"""

from d4pg_tpu.learner.state import D4PGConfig, D4PGState, init_state
from d4pg_tpu.learner.update import (
    act,
    act_deterministic,
    act_ou,
    make_multi_update,
    make_update,
    policy_params,
    update_step,
)
from d4pg_tpu.learner.fused import make_fused_chunk

__all__ = [
    "D4PGConfig",
    "D4PGState",
    "init_state",
    "act",
    "act_deterministic",
    "act_ou",
    "make_multi_update",
    "make_update",
    "policy_params",
    "update_step",
    "make_fused_chunk",
]
