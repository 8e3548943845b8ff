"""Distributional critic networks Z(s, a).

Parity: the reference critic (``models.py:51-88``): state through a 256-wide
first layer, the action concatenated at the *second* layer (``models.py:80``,
per the DDPG paper), two more 256-wide ReLU layers, then a distribution head:

  - ``categorical``: a ``n_atoms``-way softmax over fixed support bins
    (``models.py:61-62, 82-83``), fan-in init on hidden kernels and
    N(0, 3e-4) on the head (``models.py:73``).
  - ``mixture_of_gaussian``: an empty TODO stub in the reference
    (``models.py:63-65, 85-87``; ``ddpg.py:48-50, 224-226``). Implemented
    for real here: the head emits component logits, means and softplus stds
    of a K-component Gaussian mixture over returns.

The categorical critic returns *probabilities* (post-softmax) to match the
reference's forward (``models.py:82``); ``logits`` are also exposed since the
cross-entropy loss is more stable computed from log-softmax.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import flax.linen as nn
import jax.numpy as jnp

from d4pg_tpu.core.precision import to_compute
from d4pg_tpu.models.init import fanin_init, scaled_normal


class _CriticTorso(nn.Module):
    """Shared state/action MLP torso: s -> 256 -> [.,a] -> 256 -> 256."""

    hidden: Sequence[int] = (256, 256, 256)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: jnp.ndarray, action: jnp.ndarray) -> jnp.ndarray:
        x = to_compute(obs, self.dtype)
        x = nn.relu(
            nn.Dense(self.hidden[0], kernel_init=fanin_init(), dtype=self.dtype, name="fc1")(x)
        )
        x = jnp.concatenate([x, to_compute(action, self.dtype)], axis=-1)
        for i, width in enumerate(self.hidden[1:]):
            x = nn.relu(
                nn.Dense(width, kernel_init=fanin_init(), dtype=self.dtype, name=f"fc{i + 2}")(x)
            )
        return x


class CategoricalCritic(nn.Module):
    """Z(s, a) as a categorical distribution over ``n_atoms`` return bins."""

    n_atoms: int = 51
    hidden: Sequence[int] = (256, 256, 256)
    final_init_std: float = 3e-4
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self, obs: jnp.ndarray, action: jnp.ndarray, return_logits: bool = False
    ) -> jnp.ndarray:
        x = _CriticTorso(self.hidden, self.dtype, name="torso")(obs, action)
        logits = nn.Dense(
            self.n_atoms,
            kernel_init=scaled_normal(self.final_init_std),
            dtype=self.dtype,
            name="head",
        )(x).astype(jnp.float32)
        return logits if return_logits else nn.softmax(logits, axis=-1)


class MoGParams(NamedTuple):
    """Parameters of a K-component Gaussian mixture over returns."""

    log_weights: jnp.ndarray  # [..., K] log mixture weights (log-softmaxed)
    means: jnp.ndarray  # [..., K]
    stds: jnp.ndarray  # [..., K] (positive)


class MixtureOfGaussianCritic(nn.Module):
    """Z(s, a) as a mixture of Gaussians — the reference's unimplemented
    second distribution family, built for real."""

    n_components: int = 5
    hidden: Sequence[int] = (256, 256, 256)
    final_init_std: float = 3e-4
    min_std: float = 1e-3
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs: jnp.ndarray, action: jnp.ndarray) -> MoGParams:
        x = _CriticTorso(self.hidden, self.dtype, name="torso")(obs, action)
        head = nn.Dense(
            3 * self.n_components,
            kernel_init=scaled_normal(self.final_init_std),
            dtype=self.dtype,
            name="head",
        )(x).astype(jnp.float32)
        logits, means, raw_std = jnp.split(head, 3, axis=-1)
        return MoGParams(
            log_weights=nn.log_softmax(logits, axis=-1),
            means=means,
            stds=nn.softplus(raw_std) + self.min_std,
        )
