"""D4PG train state: one pytree carrying everything the update needs.

Replaces the reference's scattered mutable state — actor/critic + target
copies as four nn.Modules (``ddpg.py:57-64``), two (dead) local Adams
(``ddpg.py:67-68``), the global ``SharedAdam`` pair living in OS shared
memory (``shared_adam.py:3-17``, ``main.py:384-385``), and the shared step
counter (``main.py:386``) — with a single immutable pytree that is donated
through the jit'd update and checkpointed atomically by Orbax.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import Array

from d4pg_tpu.core.distribution import CategoricalSupport
from d4pg_tpu.core.updates import hard_update, tie_encoder
from d4pg_tpu.models.actor import Actor
from d4pg_tpu.models.critic import CategoricalCritic, MixtureOfGaussianCritic
from d4pg_tpu.models.encoder import PixelActor, PixelCategoricalCritic
from d4pg_tpu.models.torso import TorsoCritic, TorsoSpec, build_torso
from d4pg_tpu.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class D4PGConfig:
    """Static (hashable) configuration closed over by the jit'd update.

    Defaults mostly mirror the reference's (``main.py:33-49``,
    ``ddpg.py:81-87``): tau 0.001, gamma 0.99, 51 atoms. DOCUMENTED
    DIVERGENCE: the reference runs Adam with betas (0.9, 0.9) at lr 1e-3
    (``shared_adam.py:4``, ``main.py:384``). The fast-decaying second moment
    makes effective steps so large the tanh actor slams into saturation and
    its gradient vanishes (verified: on a known-optimum bandit the actor
    sticks at a=1.0 and never recovers; with b2=0.999 it converges). We
    default to standard b2=0.999 and actor lr 1e-4; set
    ``adam_b2=0.9, lr_actor=1e-3`` for strict reference parity.
    ``critic_family`` selects the distribution head: 'categorical' (live in
    the reference) or 'mog' (its empty TODO stub, implemented for real
    here).
    """

    obs_dim: int
    act_dim: int
    v_min: float = -300.0
    v_max: float = 0.0
    n_atoms: int = 51
    hidden: Sequence[int] = (256, 256, 256)
    critic_family: str = "categorical"  # 'categorical' | 'mog'
    n_components: int = 5  # MoG components
    lr_actor: float = 1e-4
    lr_critic: float = 1e-3
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    tau: float = 0.001
    gamma: float = 0.99
    # HER-recipe action-L2 penalty coefficient on the actor loss (0 = the
    # reference's plain expected-Q objective)
    action_l2: float = 0.0
    pixels: bool = False  # conv-encoder path (BASELINE.md config #4)
    obs_shape: tuple = ()  # [H, W, C] when pixels=True
    encoder_channels: tuple = (32, 32, 32, 32)  # conv widths (pixels only)
    # batch augmentation inside the jit'd update (pixels only): 'none' or
    # 'shift' (DrQ random shift, ops/augment.py — the standard antidote to
    # conv-encoder overfitting at small replay scales)
    augment: str = "none"
    augment_pad: int = 4  # DrQ's +-4px shift radius
    # Share the conv encoder between critic and actor (pixels only): the
    # encoder is trained by the CRITIC loss alone; the actor consumes it
    # through a stop-gradient and its own encoder subtree is hard-tied to
    # the critic's after every critic step. This is the SAC-AE/DrQ result
    # that makes pixel control work at small data scales — actor-gradient
    # -trained conv encoders optimize their losses while greedy returns
    # stay at the random-policy level (measured: docs/evidence/dmc-pixels/).
    # Param-tree layout is unchanged (the actor still CARRIES an encoder
    # subtree, it is just tied), so acting, weight publishing, checkpoints
    # and resume are oblivious; a run can even flip the flag mid-stream.
    share_encoder: bool = False
    mog_samples: int = 32
    # MXU compute dtype for the network matmuls ('float32' | 'bfloat16').
    # Params, optimizer state, losses and the projection stay float32;
    # bf16 matmuls measure ~1.5x the fused-dispatch update throughput.
    compute_dtype: str = "float32"
    # One value, 'einsum' (core/distribution.categorical_projection). Kept
    # only because benchmark/configs/*.json carry the key into D4PGConfig.
    projection: str = "einsum"
    # A sequence torso shared by actor and critic (models/torso.py): a
    # ``TorsoSpec``, or the dict a configuration file's ``model.torso``
    # block holds (frozen here). The observation stays a flat float32
    # vector of ``torso.tokens`` values; the torso's parameters live in the
    # critic's tree ONLY (stored once: parameter, gradient, two Adam
    # moments, target copy), the actor's tree is its head, and the torso is
    # trained by the critic loss alone (``update._torso_critic_loss``).
    torso: Any = None

    def __post_init__(self):
        if isinstance(self.torso, dict):
            object.__setattr__(self, "torso", TorsoSpec.from_dict(self.torso))
        if self.torso is not None:
            if self.pixels or self.critic_family != "categorical":
                raise ValueError(
                    "a torso reads flat float32 observations under the "
                    "categorical critic")
            if self.obs_dim != self.torso.tokens:
                raise ValueError(
                    f"obs_dim {self.obs_dim} is not the torso's "
                    f"{self.torso.tokens} tokens")
        object.__setattr__(self, "hidden", tuple(self.hidden))
        object.__setattr__(self, "obs_shape", tuple(self.obs_shape))
        object.__setattr__(self, "encoder_channels",
                           tuple(self.encoder_channels))
        if self.critic_family not in ("categorical", "mog"):
            raise ValueError(f"unknown critic_family {self.critic_family!r}")
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown compute_dtype {self.compute_dtype!r}")
        if self.projection != "einsum":
            raise ValueError(f"unknown projection {self.projection!r}")
        if self.augment not in ("none", "shift"):
            raise ValueError(f"unknown augment {self.augment!r}")
        if self.augment != "none" and not self.pixels:
            raise ValueError(
                "--augment is an image augmentation; it requires the "
                "pixel (conv-encoder) observation path")
        if self.augment != "none" and self.augment_pad < 1:
            raise ValueError(
                f"--augment {self.augment} with augment_pad="
                f"{self.augment_pad} would silently train UNaugmented; "
                "set a positive shift radius (or --augment none)")
        if self.share_encoder and not (
                self.pixels and self.critic_family == "categorical"):
            raise ValueError(
                "--share_encoder ties the actor's conv encoder to the "
                "critic's; it requires the pixel path with the "
                "categorical critic")

    @property
    def _dtype(self):
        return jnp.bfloat16 if self.compute_dtype == "bfloat16" else jnp.float32

    @property
    def support(self) -> CategoricalSupport:
        return CategoricalSupport(self.v_min, self.v_max, self.n_atoms)

    @property
    def obs_spec(self) -> int | tuple:
        """Replay/folder storage spec: [H, W, C] for pixels, else obs_dim."""
        return tuple(self.obs_shape) if self.pixels else self.obs_dim

    def build_actor(self) -> nn.Module:
        """With a torso this is the head alone: it reads the latent."""
        if self.pixels:
            # share_encoder => the policy loss must not train the (tied)
            # encoder: stop the gradient at the latent. Same param tree.
            return PixelActor(self.act_dim, channels=self.encoder_channels,
                              hidden=self.hidden, dtype=self._dtype,
                              detach_encoder=self.share_encoder)
        return Actor(self.act_dim, hidden=self.hidden, dtype=self._dtype)

    def build_critic(self) -> nn.Module:
        if self.torso is not None:
            return TorsoCritic(
                build_torso(self.torso, self._dtype),
                CategoricalCritic(self.n_atoms, hidden=self.hidden,
                                  dtype=self._dtype))
        if self.critic_family == "mog":
            return MixtureOfGaussianCritic(
                self.n_components, hidden=self.hidden, dtype=self._dtype
            )
        if self.pixels:
            return PixelCategoricalCritic(
                self.n_atoms, channels=self.encoder_channels,
                hidden=self.hidden, dtype=self._dtype
            )
        return CategoricalCritic(self.n_atoms, hidden=self.hidden, dtype=self._dtype)

    def optimizer(self, lr: float) -> optax.GradientTransformation:
        return optax.adam(lr, b1=self.adam_b1, b2=self.adam_b2)

    def dummy_obs(self) -> Array:
        shape = self.obs_shape if self.pixels else (self.obs_dim,)
        return jnp.zeros((1,) + tuple(shape), jnp.float32)


class D4PGState(NamedTuple):
    """The complete learner state; a pure pytree (jit/donate/checkpoint-able)."""

    actor_params: Any
    critic_params: Any
    target_actor_params: Any
    target_critic_params: Any
    actor_opt_state: Any
    critic_opt_state: Any
    key: Array  # PRNG key threaded through MoG sampling / any stochastic op
    step: Array  # int32 learner step counter (replaces shared global_count)


def init_state(config: D4PGConfig, key: Array) -> D4PGState:
    """Initialize networks, targets (hard-copied, ``ddpg.py:92-94``) and
    optimizer states."""
    with obs_trace.span("learner.init_state"):
        return _init_state(config, key)


def _init_state(config: D4PGConfig, key: Array) -> D4PGState:
    k_actor, k_critic, k_state = jax.random.split(key, 3)
    obs = config.dummy_obs()
    act = jnp.zeros((1, config.act_dim), jnp.float32)
    actor_params = config.build_actor().init(
        k_actor, obs if config.torso is None
        else jnp.zeros((1, config.torso.hidden_size), jnp.float32))
    critic_params = config.build_critic().init(k_critic, obs, act)
    if config.share_encoder:
        # the tie holds from step 0: otherwise the target actor starts as
        # a hard copy of an UNRELATED random encoder and the mismatch only
        # decays at (1-tau)^t through the soft updates (~thousands of
        # early bootstrap targets through a wrong encoder/MLP pairing)
        actor_params = tie_encoder(actor_params, critic_params)
    return D4PGState(
        actor_params=actor_params,
        critic_params=critic_params,
        target_actor_params=hard_update(None, actor_params),
        target_critic_params=hard_update(None, critic_params),
        actor_opt_state=config.optimizer(config.lr_actor).init(actor_params),
        critic_opt_state=config.optimizer(config.lr_critic).init(critic_params),
        key=k_state,
        step=jnp.zeros((), jnp.int32),
    )
