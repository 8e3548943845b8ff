"""The D4PG update and action functions, as pure jittable transforms.

Parity map to the reference's ``DDPG.train`` (``ddpg.py:200-255``, SURVEY.md
S2), all fused into one XLA computation:

  - target dist ``Z'(s', pi'(s'))``          ``ddpg.py:205-206``
  - Bellman projection onto the support      ``ddpg.py:214`` (host numpy
    there; MXU einsum here, ``core/distribution.py``)
  - cross-entropy critic loss                ``ddpg.py:217``
  - per-sample TD error for PER              ``ddpg.py:220-222``
  - critic Adam step                         ``ddpg.py:229-232``
  - policy loss ``-E[Z(s, pi(s))]``          ``ddpg.py:236-238``
  - actor Adam step                          ``ddpg.py:241-244``
  - soft target update (tau)                 ``ddpg.py:250, 110-116``
  - step counter increment                   ``main.py:307``

The hogwild machinery (``copy_gradients`` aliasing ``ddpg.py:104-108``,
``sync_local_global`` ``ddpg.py:118-120``, ``SharedAdam``) has no equivalent:
under pjit the gradients are all-reduced synchronously across the mesh's
``data`` axis by XLA-inserted collectives, so every replica applies the same
deterministic update (SURVEY.md §5 race-detection note).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import Array

from d4pg_tpu.core import mog as mog_ops
from d4pg_tpu.core.distribution import categorical_projection
from d4pg_tpu.core.losses import (
    categorical_td_loss,
    cross_entropy_per_sample,
    expected_q,
    weighted_mean,
)
from d4pg_tpu.core.updates import soft_update, tie_encoder
from d4pg_tpu.learner.state import D4PGConfig, D4PGState
from d4pg_tpu.models.torso import exit_distribution
from d4pg_tpu.replay.uniform import TransitionBatch


def _critic_loss_fn(
    config: D4PGConfig,
    critic_params: Any,
    state: D4PGState,
    batch: TransitionBatch,
    is_weights: Array | None,
    key: Array,
) -> tuple[Array, Array]:
    """Returns (scalar critic loss, per-sample TD error)."""
    actor = config.build_actor()
    critic = config.build_critic()
    next_action = actor.apply(state.target_actor_params, batch.next_obs)

    if config.critic_family == "mog":
        target_params = critic.apply(
            state.target_critic_params, batch.next_obs, next_action
        )
        target = mog_ops.mog_target(target_params, batch.reward, batch.discount)
        pred = critic.apply(critic_params, batch.obs, batch.action)
        return mog_ops.mog_td_loss(
            pred, target, key, n_samples=config.mog_samples, weights=is_weights
        )

    target_probs = critic.apply(
        state.target_critic_params, batch.next_obs, next_action
    )
    pred_probs = critic.apply(critic_params, batch.obs, batch.action)
    proj = jax.lax.stop_gradient(
        categorical_projection(
            config.support, target_probs, batch.reward, batch.discount)
    )
    return categorical_td_loss(proj, pred_probs, weights=is_weights)


def _actor_loss_fn(
    config: D4PGConfig,
    actor_params: Any,
    critic_params: Any,
    batch: TransitionBatch,
) -> Array:
    """Negative expected Q through the (fixed) critic (``ddpg.py:236-238``),
    plus the HER recipe's optional action-L2 penalty (``action_l2 *
    mean(a^2)`` over all elements — the OpenAI-baselines normalization, so
    published Fetch coefficients transfer regardless of act_dim)
    discouraging saturated tanh actions on sparse-reward manipulation
    tasks. With ``action_l2 > 0`` the reported ``actor_loss`` / ``q_mean``
    metrics include the penalty term."""
    # With share_encoder the actor module stops the gradient at the
    # latent (PixelActor.detach_encoder — SAC-AE/DrQ: the policy loss
    # trains ONLY the actor MLP; the tied encoder learns from the critic
    # loss alone, see the tie in update_step).
    actor = config.build_actor()
    critic = config.build_critic()
    action = actor.apply(actor_params, batch.obs)
    penalty = config.action_l2 * jnp.mean(jnp.square(action))
    if config.critic_family == "mog":
        params = critic.apply(critic_params, batch.obs, action)
        return -jnp.mean(mog_ops.mog_mean(params)) + penalty
    probs = critic.apply(critic_params, batch.obs, action)
    return -jnp.mean(expected_q(config.support, probs)) + penalty


def _augment(config: D4PGConfig, batch: TransitionBatch, key: Array):
    """The plain family's batch hook: DrQ random shift on the sampled rows
    (ops/augment.py). Both losses see the same augmented view; obs and
    next_obs get independent shifts (DrQ's convention: the target should
    not share the online view's crop). Returns the batch and the key the
    critic loss draws from."""
    if config.augment != "shift":
        return batch, key
    key, k_obs, k_next = jax.random.split(key, 3)
    from d4pg_tpu.ops.augment import random_shift

    with jax.named_scope("update.augment"):
        batch = batch._replace(
            obs=random_shift(k_obs, batch.obs, config.augment_pad),
            next_obs=random_shift(k_next, batch.next_obs,
                                  config.augment_pad),
        )
    return batch, key


def _plain_critic_loss(config, state, batch, is_weights, key):
    def loss_fn(p):
        loss, td = _critic_loss_fn(config, p, state, batch, is_weights, key)
        return loss, (loss, td, {})

    return loss_fn


def _plain_actor_loss(config, critic_params, batch):
    return lambda p: _actor_loss_fn(config, p, critic_params, batch)


def _expected_exit_loss(critic, params, proj, action, is_weights, beta,
                        latents, logits):
    """A looped torso's critic loss (Ouro's entropy-regularised objective
    with a uniform prior, arXiv:2510.25741): ``l[t, i]`` the categorical TD
    loss of the one critic head on pass ``t``'s latent of row ``i``, ``p``
    the row's exit distribution (``torso.exit_distribution``); the loss is
    the weighted mean over rows of ``sum_t p l`` less ``beta`` times the mean
    entropy of ``p``. Returns ``(total, (first term, l of the last pass,
    counters))``; ``exit_dist [R]`` is the mean over rows of ``p``,
    ``loss_by_pass [R]`` the weighted mean of ``l`` a pass."""
    td = jax.vmap(lambda z: cross_entropy_per_sample(
        proj, critic.of_latent(params, z, action)))(latents)
    p, entropy = exit_distribution(logits)
    with jax.named_scope("torso.exit"):
        loss = weighted_mean(jnp.sum(p * td, axis=0), is_weights)
        counters = {
            "exit_dist": jnp.mean(p, axis=1),
            "loss_by_pass": jax.vmap(
                lambda l: weighted_mean(l, is_weights))(td)}
        return loss - beta * jnp.mean(entropy), (loss, td[-1], counters)


def _torso_critic_loss(config, state, batch, is_weights, key):
    """Passes 1 and 2 of a model with a shared sequence torso
    (``config.torso``, models/torso.py). The torso is stored once, in the
    critic's tree, and run once per input per phase: three forward passes
    and one backward a step.

      1. the TARGET torso on ``next_obs``; target actor and target critic
         heads read its latent (the Bellman target);
      2. the torso on ``obs`` under the critic loss, differentiated: the
         critic loss alone trains it (as the shared pixel encoder), but for
         a sparse-attention layer's indexer: nothing of the critic loss
         reaches it (the selection is not differentiable), so the torso's
         ``index_loss`` is added to the critic loss with no coefficient;
         the two losses' parameter sets are disjoint and both step at
         ``lr_critic``;
      3. (``_torso_actor_loss``) the torso just stepped on ``obs`` again,
         through a stop-gradient: the actor head and the stepped critic
         head read it for the actor loss, which trains the actor's head
         only.

    The aux handed up becomes metrics: ``route_counts`` ``[layers with
    experts, num_experts]`` int32, how many of pass 2's assignments the
    router gave each expert; with sparse-attention layers also
    ``select_counts`` ``[sparse layers, tokens / kv_chunk_size]`` int32
    (pass 2's selections by block of keys) and ``index_loss``; with a
    routing bias ``bias_swapped`` ``[layers with experts]`` int32, pass 2's
    assignments that the bias changed; with recurrent operators the mean
    decay a layer of pass 2, float32 (``delta_kept`` ``[DeltaNet layers]``,
    ``ssd_kept`` ``[Mamba blocks]``). ``critic_loss`` stays the TD loss
    alone.

    A looped torso (``total_ut_steps`` > 1) runs all its passes in each of
    the three; passes 1 and 3 read the last pass's latent, pass 2 every
    pass's and the exit gate's logits (``_expected_exit_loss``: the
    expectation of the TD loss over the exit distribution less an entropy
    bonus; the gradient runs through all passes into the shared leaves and
    the gate). ``critic_loss`` is the expectation alone, ``td_error`` the
    last pass's (the network actors and targets use), and the metrics gain
    ``exit_dist [R]`` and ``loss_by_pass [R]``. A torso without experts
    reports no ``route_counts``."""
    del key  # no loss of this family draws
    actor, critic = config.build_actor(), config.build_critic()
    z_next, _ = critic.latent(state.target_critic_params, batch.next_obs)
    next_action = actor.apply(state.target_actor_params, z_next)
    target_probs = critic.of_latent(state.target_critic_params, z_next,
                                    next_action)
    proj = jax.lax.stop_gradient(
        categorical_projection(config.support, target_probs,
                               batch.reward, batch.discount))

    def loss_fn(p):
        z, aux = critic.latent(p, batch.obs, train=True)
        if "exit_logits" in aux:  # a looped torso: every pass's latent
            return _expected_exit_loss(
                critic, p, proj, batch.action, is_weights,
                config.torso.exit_entropy_beta, aux["pass_latents"],
                aux["exit_logits"])
        loss, td = categorical_td_loss(
            proj, critic.of_latent(p, z, batch.action), weights=is_weights)
        total = loss + aux["index_loss"] if "index_loss" in aux else loss
        return total, (loss, td, aux)

    return loss_fn


def _torso_balance(config, critic_params, aux):
    """A router's load-balancing bias: the first torso state that no loss
    trains (its gradient is exactly zero: it enters a top-k only) and no
    optimizer steps; this pass's own load counter moves it, after the
    step, and the target's follows by soft_update."""
    return config.build_critic().balance(critic_params,
                                         aux.get("route_counts"))


def _torso_actor_loss(config, critic_params, batch):
    actor, critic = config.build_actor(), config.build_critic()
    z = jax.lax.stop_gradient(critic.latent(critic_params, batch.obs)[0])

    def loss_fn(p):
        action = actor.apply(p, z)
        probs = critic.of_latent(critic_params, z, action)
        return (-jnp.mean(expected_q(config.support, probs))
                + config.action_l2 * jnp.mean(jnp.square(action)))

    return loss_fn


class _Family(NamedTuple):
    """How a family of models reads its networks in the one update step:
    whole networks on the observation (plain), or heads on a latent a
    shared torso made once (torso). ``critic_loss`` and ``actor_loss`` run
    what is not differentiated and return the function of the parameters
    that is: ``(total, (critic_loss, td_error, aux))`` and a scalar."""
    batch_hook: Callable
    critic_loss: Callable
    post_critic: Callable
    actor_loss: Callable


_PLAIN = _Family(_augment, _plain_critic_loss,
                 lambda config, critic_params, aux: critic_params,
                 _plain_actor_loss)
_TORSO = _Family(lambda config, batch, key: (batch, key), _torso_critic_loss,
                 _torso_balance, _torso_actor_loss)


def update_step(
    config: D4PGConfig,
    state: D4PGState,
    batch: TransitionBatch,
    is_weights: Array | None = None,
) -> tuple[D4PGState, dict[str, Array]]:
    """One full D4PG update. Pure; jit with config static.

    Returns the new state and a metrics dict containing scalar ``critic_loss``
    / ``actor_loss`` / ``q_mean``, the per-sample ``td_error`` vector (the
    PER priority signal, ``ddpg.py:252-255``) and what the family's critic
    loss handed up (``_torso_critic_loss``; nothing for a plain model).
    """
    family = _PLAIN if config.torso is None else _TORSO
    key, sub = jax.random.split(state.key)
    batch, sub = family.batch_hook(config, batch, sub)

    # --- critic step. The named scopes (update.augment in the hook,
    # update.critic, update.actor, update.optim) are metadata only; a
    # trace reader splits the fused chunk's ``learner.update`` phase by
    # them (PERF.md section 3) ----------------------------------------------
    with jax.named_scope("update.critic"):
        (_, (critic_loss, td_error, aux)), critic_grads = jax.value_and_grad(
            family.critic_loss(config, state, batch, is_weights, sub),
            has_aux=True,
        )(state.critic_params)
    with jax.named_scope("update.optim"):
        critic_updates, critic_opt_state = config.optimizer(
            config.lr_critic).update(
            critic_grads, state.critic_opt_state, state.critic_params)
        critic_params = optax.apply_updates(state.critic_params,
                                            critic_updates)
        critic_params = family.post_critic(config, critic_params, aux)

    # --- shared-encoder tie (SAC-AE/DrQ): the actor's encoder subtree IS
    # the critic's, refreshed right after the critic step. Done on the
    # params the actor step reads, and RE-asserted after apply_updates
    # below, so the invariant holds even when the actor Adam carries
    # nonzero encoder moments — e.g. a run that flipped --share_encoder
    # on when resuming an unshared checkpoint (stale moments keep
    # emitting decaying updates for many steps; overwriting, not
    # masking, makes that unobservable). The TARGET actor's encoder is
    # likewise tied to the TARGET critic's in the soft-update step — a
    # no-op for a shared-from-init run (identical EMA sequences) that
    # makes the mid-run flip exact rather than (1-tau)^t-transient.
    actor_params_in = (
        tie_encoder(state.actor_params, critic_params)
        if config.share_encoder else state.actor_params)

    # --- actor step. Documented divergence: the policy loss here flows
    # through the critic params the critic Adam step just produced. The
    # reference computes it with its LOCAL critic, which at that point
    # still predates the global optimizer step (``ddpg.py:236-249`` —
    # ``sync_local_global`` pulls the stepped weights back only at
    # ``ddpg.py:247``), i.e. the pre-update critic. Both are standard
    # D4PG variants; one-step-fresher critic is the natural fit for a
    # single fused XLA computation (like the (0.9, 0.999) Adam-b2 default,
    # ``learner/state.py:34-41``). -----------------------------------------
    with jax.named_scope("update.actor"):
        actor_loss, actor_grads = jax.value_and_grad(
            family.actor_loss(config, critic_params, batch)
        )(actor_params_in)
    with jax.named_scope("update.optim"):
        actor_updates, actor_opt_state = config.optimizer(
            config.lr_actor).update(
            actor_grads, state.actor_opt_state, actor_params_in)
        actor_params = optax.apply_updates(actor_params_in, actor_updates)
        if config.share_encoder:
            actor_params = tie_encoder(actor_params, critic_params)

        # --- soft target updates (tau, ``ddpg.py:110-116``) ---------------
        target_actor_params = soft_update(
            state.target_actor_params, actor_params, config.tau
        )
        target_critic_params = soft_update(
            state.target_critic_params, critic_params, config.tau
        )
        if config.share_encoder:
            target_actor_params = tie_encoder(
                target_actor_params, target_critic_params)
    new_state = D4PGState(
        actor_params=actor_params,
        critic_params=critic_params,
        target_actor_params=target_actor_params,
        target_critic_params=target_critic_params,
        actor_opt_state=actor_opt_state,
        critic_opt_state=critic_opt_state,
        key=key,
        step=state.step + 1,
    )
    metrics = {
        "critic_loss": critic_loss,
        "actor_loss": actor_loss,
        "q_mean": -actor_loss,
        "td_error": td_error,
        **aux,
    }
    return new_state, metrics


def mesh_shardings(config: D4PGConfig, mesh, td_error) -> tuple[Any, dict]:
    """The state's shardings (by partition rule) and the metrics' (scalars
    replicated, ``td_error`` as given) for a program over ``mesh``: the one
    place a mesh enters the learner's builders, so the one place it is
    refused (``check_mesh_compatible``)."""
    from d4pg_tpu.parallel import partition
    from d4pg_tpu.parallel.data_parallel import check_mesh_compatible

    check_mesh_compatible(config)
    repl = partition.replicated(mesh)
    return partition.state_shardings(config, mesh), {
        "critic_loss": repl, "actor_loss": repl, "q_mean": repl,
        "td_error": td_error}


def _jit_update(fn, config: D4PGConfig, mesh, donate: bool, stacked: bool):
    """jit ``fn(state, batch, weights)``; with a mesh, data-parallel over
    it: batch and IS weights ([B, ...]; ``stacked`` [K, B, ...]) with B
    split over ``data``, ``td_error`` like them (it flows back to the host
    PER update, ``ddpg.py:252-255``). A sharding is a pytree prefix: one
    covers a tree, and no leaf of a ``None``. The loss's mean over the
    global batch becomes an all-reduce over ICI; every replica then applies
    the same Adam update."""
    over = {}
    if mesh is not None:
        from d4pg_tpu.parallel import partition

        rows = (partition.stacked_sharding if stacked
                else partition.batch_sharding)(mesh)
        state_sh, metrics_sh = mesh_shardings(config, mesh, td_error=rows)
        over = dict(in_shardings=(state_sh, rows, rows),
                    out_shardings=(state_sh, metrics_sh))
    return jax.jit(fn, donate_argnums=(0,) if donate else (), **over)


def make_update(config: D4PGConfig, *, mesh=None, donate: bool = True):
    """jit the update with ``config`` closed over statically:
    ``fn(state, batch, is_weights) -> (state, metrics)``. Uniform replay
    passes ``None`` for the weights: an empty pytree, no operand.

    ``donate=True`` donates the input state's buffers so XLA updates
    parameters in place (HBM-frugal)."""
    return _jit_update(
        lambda state, batch, w: update_step(config, state, batch, w),
        config, mesh, donate, stacked=False)


def multi_update_step(
    config: D4PGConfig,
    state: D4PGState,
    batches: TransitionBatch,
    weights: Array | None = None,
):
    """K sequential updates via ``lax.scan`` over stacked batches — the pure
    function behind :func:`make_multi_update`.

    Inputs carry a leading K axis: batch fields [K, B, ...], weights
    [K, B]. Returns ``(state, metrics)`` with metrics stacked along K
    (``td_error`` [K, B] feeds the batched priority write-back).
    """
    # ``weights=None`` scans as it is: an empty pytree, ``None`` every step
    return jax.lax.scan(lambda s, xs: update_step(config, s, *xs), state,
                        (batches, weights))


def make_multi_update(config: D4PGConfig, *, mesh=None, donate: bool = True):
    """jit :func:`multi_update_step`, K updates per device dispatch:
    ``fn(state, batches, weights) -> (state, metrics)``, ``None`` weights
    for uniform replay. Scanning K steps amortizes the dispatch of a step
    whose compute is tens of microseconds; with a mesh the scan axis K
    stays replicated. Semantically identical to K sequential
    ``update_step`` calls (the PRNG chain threads through the carried
    state); for PER the K priority updates land after the scan, i.e. with
    staleness < K (standard in high-throughput actor-learner pipelines).
    """
    return _jit_update(
        lambda state, batches, w: multi_update_step(config, state, batches,
                                                    w),
        config, mesh, donate, stacked=True)


def policy_params(config: D4PGConfig, state: D4PGState) -> Any:
    """What acting needs of a learner state, and what the weight plane
    publishes: the actor's tree; with a torso, the actor's head beside the
    critic's torso (the one place it is stored)."""
    if config.torso is None:
        return state.actor_params
    return {"params": {"actor": state.actor_params["params"],
                       "torso": state.critic_params["params"]["torso"]}}


def _policy(config: D4PGConfig, params: Any, obs: Array) -> Array:
    """The greedy action from ``policy_params``."""
    if config.torso is None:
        return config.build_actor().apply(params, obs)
    latent, _aux = config.build_critic().torso.apply(
        params["params"]["torso"], obs)
    return config.build_actor().apply({"params": params["params"]["actor"]},
                                      latent)


@partial(jax.jit, static_argnums=(0,))
def act(
    config: D4PGConfig,
    actor_params: Any,
    obs: Array,
    key: Array,
    epsilon: Array | float = 0.3,
) -> Array:
    """Exploratory action: ``clip(pi(s) + eps * N(0, I), -1, 1)``
    (``main.py:145-146`` with the Gaussian noise of ``random_process.py:16-18``).

    Batched: obs [B, obs_dim] -> actions [B, act_dim]; one key for the whole
    batch (split upstream per actor for decorrelation).
    """
    action = _policy(config, actor_params, obs)
    noise = jax.random.normal(key, action.shape) * epsilon
    return jnp.clip(action + noise, -1.0, 1.0)


@partial(jax.jit, static_argnums=(0,))
def act_deterministic(config: D4PGConfig, actor_params: Any, obs: Array) -> Array:
    """Greedy action for evaluation (``main.py:121-130``)."""
    return _policy(config, actor_params, obs)


@partial(jax.jit, static_argnums=(0,))
def act_ou(
    config: D4PGConfig,
    actor_params: Any,
    obs: Array,
    ou_state,
    key: Array,
    epsilon: Array | float = 1.0,
    theta: float = 0.25,
    mu: float = 0.0,
    sigma: float = 0.05,
    dt: float = 0.01,
):
    """Exploratory action with Ornstein-Uhlenbeck noise, fused into one jit:
    greedy forward + OU state advance + clip in a single dispatch (the
    temporally-correlated process of ``random_process.py:23-45``, which the
    reference constructs nowhere live — SURVEY.md C6).

    Returns ``(actions, new_ou_state)``; thread the state through the acting
    loop and zero rows at episode boundaries.
    """
    from d4pg_tpu.core.noise import ou

    greedy = _policy(config, actor_params, obs)
    new_state, noise = ou.sample(ou_state, key, theta=theta, mu=mu,
                                 sigma=sigma, dt=dt)
    action = jnp.clip(greedy + epsilon * noise, -1.0, 1.0)
    return action, new_state
