"""D4PG losses, expressed as pure functions over distributions.

Parity targets in the reference:
  - distributional critic loss: cross-entropy between the projected target
    distribution and the predicted distribution,
    ``-(proj * log(q + 1e-10)).sum(-1).mean()`` (``ddpg.py:217``);
  - PER priority signal (``ddpg.py:220-222``);
  - policy loss: ``-(Z(s, pi(s)) @ bin_centers).mean()`` — the negative
    expected Q through the support bin centers (``ddpg.py:236-238``).

Deviations (deliberate, documented):
  - Importance-sampling weights are *applied* to the critic loss here. The
    reference computes IS weights in its PER sampler
    (``prioritized_replay_memory.py:303-311``) but never multiplies them into
    the loss — we implement the PER algorithm as specified (Schaul et al.),
    with ``weights=None`` recovering the reference's unweighted behavior.
  - ``td_error`` offers the standard per-sample cross-entropy in addition to
    the reference's ``-(proj * q).sum(-1)`` signal (which is not a KL/CE and
    can be negative before the abs); both are available, cross-entropy is the
    default priority signal.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import Array

from d4pg_tpu.core.distribution import CategoricalSupport

_LOG_EPS = 1e-10  # matches the reference's log(q + 1e-010), ddpg.py:217


def cross_entropy_per_sample(proj: Array, pred_probs: Array) -> Array:
    """Per-sample CE between projected target and predicted distribution.

    proj, pred_probs: [..., n_atoms] -> [...].
    """
    return -jnp.sum(proj * jnp.log(pred_probs + _LOG_EPS), axis=-1)


def weighted_mean(td: Array, weights: Array | None = None) -> Array:
    """THE loss reduction: mean of per-sample errors, PER IS-weighted when
    ``weights`` is given. One definition shared by both critic families
    (categorical, MoG) so the weighting convention cannot diverge between
    them."""
    return jnp.mean(td if weights is None else weights * td)


def categorical_td_loss(
    proj: Array,
    pred_probs: Array,
    weights: Array | None = None,
) -> tuple[Array, Array]:
    """Distributional critic loss and per-sample TD error.

    Returns ``(scalar_loss, td_error)`` where ``td_error`` ([...]) is the
    per-sample cross-entropy — the PER priority signal. ``weights`` are PER
    importance-sampling weights ([...]) applied to the mean; ``None`` means
    uniform (reference behavior).
    """
    td = cross_entropy_per_sample(proj, pred_probs)
    return weighted_mean(td, weights), td


def reference_td_error(proj: Array, pred_probs: Array) -> Array:
    """The reference's exact priority signal, ``-(proj * q).sum(-1)``
    (``ddpg.py:220-222``). Provided for strict parity experiments."""
    return -jnp.sum(proj * pred_probs, axis=-1)


def expected_q(support: CategoricalSupport, probs: Array) -> Array:
    """E[Z] via the support bin centers: [..., n_atoms] -> [...]."""
    return jnp.sum(probs * support.atoms, axis=-1)


def policy_loss(support: CategoricalSupport, critic_probs: Array) -> Array:
    """Deterministic policy-gradient loss: negative mean expected Q of
    Z(s, pi(s)) (``ddpg.py:236-238``)."""
    return -jnp.mean(expected_q(support, critic_probs))
