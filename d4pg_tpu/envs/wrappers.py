"""Action rescaling and goal-observation flattening.

Parity: ``NormalizeAction`` (``normalize_env.py:3-14``) — the affine map
between the policy's tanh range (-1, 1) and the env's ``[low, high]`` action
box — and the dict-obs concatenation the reference hardwires into its
collection loop (``state['observation']`` + ``state['desired_goal']``,
``main.py:144``), here as an explicit, reusable adapter.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


def rescale_action(action: np.ndarray, low: np.ndarray, high: np.ndarray) -> np.ndarray:
    """tanh range (-1, 1) -> [low, high] (``normalize_env.py:5-8``)."""
    return low + (action + 1.0) * 0.5 * (high - low)


def inverse_rescale_action(
    action: np.ndarray, low: np.ndarray, high: np.ndarray
) -> np.ndarray:
    """[low, high] -> (-1, 1) (``normalize_env.py:10-14``)."""
    return 2.0 * (action - low) / (high - low) - 1.0


class RescaleActionWrapper:
    """gymnasium wrapper form of ``rescale_action`` for single envs."""

    def __init__(self, env):
        self.env = env
        self.low = np.asarray(env.action_space.low, np.float32)
        self.high = np.asarray(env.action_space.high, np.float32)

    def reset(self, **kw):
        return self.env.reset(**kw)

    def step(self, action):
        return self.env.step(rescale_action(np.asarray(action), self.low, self.high))

    def __getattr__(self, name):
        return getattr(self.env, name)


class GoalObs(NamedTuple):
    """Structured goal-conditioned observation (gymnasium GoalEnv dict)."""

    observation: np.ndarray
    achieved_goal: np.ndarray
    desired_goal: np.ndarray


def flatten_goal_obs(obs) -> np.ndarray:
    """Concatenate observation and desired goal into the policy input
    (``main.py:144``). Accepts a GoalObs, a gymnasium dict, or a plain
    array (returned unchanged)."""
    if isinstance(obs, GoalObs):
        return np.concatenate([obs.observation, obs.desired_goal], axis=-1)
    if isinstance(obs, dict):
        return np.concatenate([obs["observation"], obs["desired_goal"]], axis=-1)
    return np.asarray(obs)


class History:
    """The last steps of a state-vector env as one flat float32 vector of
    ``width`` values: what a sequence torso tokenises (models/torso.py).

    Each step contributes its observation and the action that led to it
    (zeros at reset); as many whole steps as fit are kept, oldest first,
    and the rest of the vector is zero: Humanoid-v4's 376 + 17 values make
    ten steps of 4,096. ``reset`` fills the history with the first step.
    Frame stacking for vectors, as ``FrameStack`` is for pixels: the replay
    row stays a flat vector and the sequence lives inside the model."""

    def __init__(self, env, width: int):
        from collections import deque

        import gymnasium.spaces

        self.env = env
        self._act = int(np.prod(env.action_space.shape))
        step = int(np.prod(env.observation_space.shape)) + self._act
        self._steps = int(width) // step
        if self._steps < 1:
            raise ValueError(
                f"a history of {width} values holds no whole step of "
                f"{step} (observation and action)")
        self._width = int(width)
        self._rows: "deque" = deque(maxlen=self._steps)
        self.observation_space = gymnasium.spaces.Box(
            low=-np.inf, high=np.inf, shape=(self._width,), dtype=np.float32)
        self.action_space = env.action_space

    def _row(self, obs, action):
        return np.concatenate([np.asarray(obs, np.float32).ravel(),
                               np.asarray(action, np.float32).ravel()])

    def _flat(self):
        out = np.zeros((self._width,), np.float32)
        rows = np.concatenate(list(self._rows))
        out[:rows.size] = rows
        return out

    def reset(self, **kw):
        obs, info = self.env.reset(**kw)
        row = self._row(obs, np.zeros((self._act,), np.float32))
        for _ in range(self._steps):
            self._rows.append(row)
        return self._flat(), info

    def step(self, action):
        obs, reward, terminated, truncated, info = self.env.step(action)
        self._rows.append(self._row(obs, action))
        return self._flat(), reward, terminated, truncated, info

    def close(self):
        return self.env.close()


class FrameStack:
    """Stack the last ``k`` pixel observations along the channel axis.

    Pixel control from a SINGLE frame is a POMDP — velocities are
    invisible, so tasks like cartpole-swingup (which way is the pole
    moving?) are structurally unlearnable. Stacking k frames restores the
    Markov property the state-vector path gets for free; every published
    pixel-control baseline (DQN's 4-stack; DrQ/D4PG-pixels' 3-stack) does
    this. The reference has no pixel path at all (``models.py:15`` is
    state-only), so this wrapper has no reference analogue.

    [H, W, C] -> [H, W, C*k], newest frame LAST (channels-concatenated);
    ``reset`` fills the buffer with k copies of the first frame. uint8
    in, uint8 out — the replay ring stores stacked rows directly.
    """

    def __init__(self, env, k: int):
        from collections import deque

        if k < 1:
            raise ValueError(f"frame_stack must be >= 1, got {k}")
        self.env = env
        self._k = int(k)
        self._frames: "deque" = deque(maxlen=self._k)
        space = env.observation_space
        if len(space.shape) != 3:
            raise ValueError(
                f"FrameStack wraps pixel [H, W, C] observations, got "
                f"shape {space.shape}")
        h, w, c = space.shape
        import gymnasium.spaces

        # duck-typed spaces (the fake test envs) may lack .dtype; the
        # bound arrays always carry one (possibly wider than the actual
        # frames — dims/dtype downstream come from a real reset obs in
        # train.infer_dims, not from this advertisement). tile, not
        # repeat: the data layout is whole frames concatenated
        # [c0,c1,c2, c0,c1,c2, ...], so per-channel bounds must tile in
        # the same order.
        dtype = getattr(space, "dtype", None) or space.low.dtype
        self.observation_space = gymnasium.spaces.Box(
            low=np.tile(np.asarray(space.low), (1, 1, self._k)),
            high=np.tile(np.asarray(space.high), (1, 1, self._k)),
            shape=(h, w, c * self._k),
            dtype=dtype,
        )
        self.action_space = env.action_space

    def _stacked(self):
        return np.concatenate(list(self._frames), axis=-1)

    def reset(self, **kw):
        obs, info = self.env.reset(**kw)
        for _ in range(self._k):
            self._frames.append(obs)
        return self._stacked(), info

    def step(self, action):
        obs, reward, terminated, truncated, info = self.env.step(action)
        self._frames.append(obs)
        return self._stacked(), reward, terminated, truncated, info

    def close(self):
        if hasattr(self.env, "close"):
            self.env.close()
