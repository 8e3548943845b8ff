"""Evaluator: periodic greedy rollouts against the latest published weights.

Parity: the reference's evaluator process (``global_model_eval``,
``main.py:103-134``): copy global weights, run a greedy episode, track the
0.95/0.05 EWMA of returns, repeat — plus the per-cycle 10-trial eval with
success-rate (``main.py:309-347``). Here the evaluator pulls from the
``WeightStore`` (no shared memory) and reports through a metrics callback
instead of appending to a process-local list the parent never sees
(the reference's ``global_returns`` bug, SURVEY.md C17).
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Optional

import numpy as np

from d4pg_tpu.envs.wrappers import flatten_goal_obs, rescale_action
from d4pg_tpu.obs.containment import contained_crash
from d4pg_tpu.learner.state import D4PGConfig
from d4pg_tpu.distributed.weights import WeightStore
from d4pg_tpu.serving.client import ActorConfig, LocalPolicyClient

EWMA_OLD, EWMA_NEW = 0.95, 0.05  # main.py:131


class Evaluator:
    def __init__(
        self,
        config: D4PGConfig,
        env_fn: Callable[[], object],
        weights: WeightStore,
        max_steps: int = 1000,
        goal_conditioned: bool = False,
        device: str = "cpu",
        obs_norm=None,
    ):
        self.config = config
        self.env = env_fn()
        self.weights = weights
        self.max_steps = max_steps
        self.goal_conditioned = goal_conditioned
        # shared RunningMeanStd: the policy was trained on normalized obs,
        # so greedy eval must apply the same (current) statistics — read
        # only, never updated from eval rollouts
        self.obs_norm = obs_norm
        self.ewma_return: Optional[float] = None
        low = np.asarray(self.env.action_space.low, np.float32)
        high = np.asarray(self.env.action_space.high, np.float32)
        self._low, self._high = low, high
        # Greedy rollouts are batch-1 inference per env step — pinned to the
        # host CPU backend by default for the same reason as ActorConfig
        # .device: a per-step accelerator round trip costs more than the MLP
        # forward, and eval must not contend with the learner's chip. Since
        # the serving split, the query path is the same PolicyClient the
        # actors use (greedy mode) instead of a duplicated inline dispatch.
        self.policy = LocalPolicyClient(
            config, ActorConfig(device=device), weights)

    def _device_scope(self):
        return self.policy._device_scope()

    def _greedy_episode(self, seed: int | None = None) -> tuple[float, bool]:
        reset_kw = {"seed": seed} if seed is not None else {}
        obs, _ = self.env.reset(**reset_kw)
        total, success = 0.0, False
        for _ in range(self.max_steps):
            flat = flatten_goal_obs(obs)
            if self.obs_norm is not None:
                flat = self.obs_norm.normalize(flat)
            a = self.policy.greedy_actions(flat[None])[0]
            obs, r, term, trunc, info = self.env.step(
                rescale_action(a, self._low, self._high)
            )
            total += float(r)
            success = success or bool(info.get("is_success", False))
            if term or trunc:
                break
        return total, success

    def evaluate(self, n_trials: int = 10, seed: int | None = None) -> dict:
        """Run n greedy trials; returns metrics incl. EWMA'd return and
        success rate (``main.py:309-353``)."""
        # Snapshot step WITH the params: the learner may publish again while
        # the rollouts run, and ``learner_step`` must describe the weights
        # actually evaluated (it feeds the eval_lag_steps metric).
        # snapshot_pull adopts the store's CURRENT params regardless of
        # version — eval must not skip a re-publish of the same version.
        _, published_step = self.policy.snapshot_pull()
        returns, successes = [], []
        for i in range(n_trials):
            ep_seed = None if seed is None else seed + i
            ret, suc = self._greedy_episode(ep_seed)
            returns.append(ret)
            successes.append(suc)
        avg = float(np.mean(returns))
        if self.ewma_return is None:
            self.ewma_return = avg
        else:
            self.ewma_return = EWMA_OLD * self.ewma_return + EWMA_NEW * avg
        return {
            "avg_test_reward": avg,
            "ewma_test_reward": self.ewma_return,
            "success_rate": float(np.mean(successes)),
            "learner_step": published_step,
        }


class AsyncEvaluator:
    """Concurrent evaluation off the learner thread.

    The reference evaluates in a SEPARATE process while training continues
    (``main.py:395-397``); round 1 ran ``Evaluator.evaluate`` inline on the
    learner thread, stalling every cycle for the rollouts. This wrapper owns
    a background thread: the learner ``request()``s an eval (non-blocking;
    coalesced if one is already running) and reads the most recent completed
    result via ``latest()``. Results carry the ``learner_step`` the weights
    were published at, so the logged ``eval_lag_steps`` is observable.
    """

    def __init__(self, evaluator: Evaluator):
        self._ev = evaluator
        self._requests: queue.Queue = queue.Queue(maxsize=1)
        self._latest: Optional[dict] = None
        self._lock = threading.Lock()
        # Accepted-but-not-finished request count. Incremented in request()
        # BEFORE the queue put and decremented only after the eval (or its
        # failure) completes, so wait() cannot slip through the window
        # between the worker's dequeue and the start of the rollouts.
        self._outstanding = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def request(self, n_trials: int, seed: int | None = None) -> bool:
        """Enqueue an eval against the CURRENT WeightStore contents. Returns
        False (dropped) if an eval is already queued — the learner never
        waits."""
        with self._lock:
            self._outstanding += 1
        try:
            self._requests.put_nowait((n_trials, seed))
            return True
        except queue.Full:
            with self._lock:
                self._outstanding -= 1
            return False

    def latest(self) -> Optional[dict]:
        """Most recent completed eval metrics (None until the first one)."""
        with self._lock:
            return None if self._latest is None else dict(self._latest)

    def wait(self, timeout: float = 300.0) -> Optional[dict]:
        """Drain pending requests and return the final metrics (shutdown /
        end-of-training path)."""
        import time

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if self._outstanding == 0:
                    break
            time.sleep(0.01)
        return self.latest()

    def _loop(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    n_trials, seed = self._requests.get(timeout=0.1)
                except queue.Empty:
                    continue
                try:
                    result = self._ev.evaluate(n_trials, seed=seed)
                    with self._lock:
                        self._latest = result
                except Exception as e:  # noqa: BLE001 — eval crash must not kill training
                    print(f"evaluator failed: {e!r}", flush=True)
                    # counted, so a run that "finished" without its evals
                    # shows threads.contained_crashes > 0
                    contained_crash("evaluator.evaluate", e)
                finally:
                    with self._lock:
                        self._outstanding -= 1
        except Exception as e:
            contained_crash("evaluator.loop", e)

    def close(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10.0)
