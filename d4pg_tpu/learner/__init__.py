"""Learner core: train state + the single jit'd D4PG update.

The reference's hot loop (``ddpg.py:200-255``, call stack SURVEY.md S2) spans
torch autograd, a host-side numpy projection round-trip, shared-memory
optimizers and python parameter loops. Here the entire update — target
forward, Bellman projection, both losses, gradients, Adam, soft target
update, TD-error outputs for PER — is ONE jit'd XLA computation; only replay
sampling and priority writes stay on host.
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
from d4pg_tpu.learner.fused import make_fused_chunk, make_sharded_fused_chunk

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
    "make_sharded_fused_chunk",
]
