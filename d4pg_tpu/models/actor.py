"""Deterministic policy network pi(s) -> a in (-1, 1)^act_dim.

Parity: the reference actor (``models.py:15-41``): MLP with hidden widths
256-256-256, tanh-bounded output, fan-in init on hidden kernels, N(0, 3e-3)
on the output kernel. The reference forgot the activation between its second
and third hidden layers (``models.py:36-37`` — two consecutive Linears);
per SURVEY.md §7 we do NOT reproduce that quirk: every hidden layer here is
followed by ReLU.

TPU notes: hidden widths are configurable (default 256) and should be kept
multiples of 128 so XLA tiles the matmuls onto the MXU cleanly; compute dtype
is configurable for bfloat16 inference on actors.
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from d4pg_tpu.core.precision import to_compute
from d4pg_tpu.models.init import fanin_init, scaled_normal


class Actor(nn.Module):
    act_dim: int
    hidden: Sequence[int] = (256, 256, 256)
    final_init_std: float = 3e-3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: jnp.ndarray) -> jnp.ndarray:
        x = to_compute(obs, self.dtype)
        for i, width in enumerate(self.hidden):
            x = nn.Dense(
                width, kernel_init=fanin_init(), dtype=self.dtype, name=f"fc{i + 1}"
            )(x)
            x = nn.relu(x)
        x = nn.Dense(
            self.act_dim,
            kernel_init=scaled_normal(self.final_init_std),
            dtype=self.dtype,
            name="out",
        )(x)
        return jnp.tanh(x).astype(jnp.float32)
