"""Policy clients: the query half of acting, behind one interface.

``distributed/actor.py`` used to fuse two jobs: stepping envs and
querying the policy (weight pulls, exploration noise, epsilon decay,
device pinning). The serving plane needs the query half alone — a
vectorized lane asks *something* for actions, and that something is
either in-process inference against the ``WeightStore``
(:class:`LocalPolicyClient`, the legacy behavior, extracted verbatim so
the seeded action stream is bitwise-unchanged) or a wire round trip to
a :class:`~d4pg_tpu.serving.server.PolicyInferenceServer`
(:class:`RemotePolicyClient`, SEED-style: the server owns params and
batches inference; the client owns exploration noise and degradation).

Interface contract (duck-typed; both clients honor it):

    pull() -> bool            refresh params if a newer version exists
    actions(obs) -> [B, A]    noisy exploration actions; ``obs`` is
                              ALREADY normalized by the caller (the
                              legacy ``_explore_actions`` convention)
    greedy_actions(obs)       deterministic mu(s) for evaluation
    reset_noise(done_mask)    zero per-env noise state on episode end
    decay_epsilon()           episode-boundary epsilon schedule step
    close()                   release sockets (no-op locally)
    obs_norm                  read-only normalizer view (or None)
    epsilon / version         current exploration scale / param version

The remote client never stalls an env loop: a dead or slow server is a
COUNTED degradation (timeout -> reconnect -> local cached-params or
uniform-warmup fallback), mirroring the fleet plane's no-silent-loss
rule on the ingest side.
"""

from __future__ import annotations

import contextlib
import dataclasses
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from d4pg_tpu.core.noise import ou
from d4pg_tpu.envs.normalizer import FrozenNormalizer, RunningMeanStd
from d4pg_tpu.learner.state import D4PGConfig
from d4pg_tpu.learner.update import act, act_deterministic, act_ou
from d4pg_tpu.obs.trace import new_trace_id
from d4pg_tpu.serving import protocol

# NOTE: d4pg_tpu.distributed.transport is imported lazily inside the
# remote client's connection path — a module-level import would close
# the cycle distributed/__init__ -> actor -> serving.client.


@dataclasses.dataclass
class ActorConfig:
    """Acting-plane config (exploration + env-loop knobs). Lives here so
    both policy clients and the env-stepping lanes can import it without
    a cycle; ``distributed.actor`` re-exports it unchanged."""

    epsilon_0: float = 0.3  # the reference's live, never-decayed eps (C5)
    min_epsilon: float = 0.01
    epsilon_horizon: int = 5000  # episodes to decay over (random_process.py:13)
    n_step: int = 3
    gamma: float = 0.99
    reward_scale: float = 1.0
    weight_poll_every: int = 1  # pool ticks between version checks
    # Exploration process. The reference exposes --ou_theta/--ou_sigma/--ou_mu
    # but never wires OU in (SURVEY.md C6 — constructed nowhere live); here
    # noise='ou' actually runs the temporally-correlated process.
    noise: str = "gaussian"  # 'gaussian' | 'ou'
    # Probability of replacing the policy action with a uniform random one,
    # per env per tick (the HER recipe's epsilon-greedy component — sparse
    # goal tasks need undirected exploration that additive Gaussian noise
    # around a confident wrong policy cannot provide). 0 = reference
    # behavior (additive noise only, random_process.py:16-18).
    random_eps: float = 0.0
    ou_theta: float = 0.25
    ou_sigma: float = 0.05
    ou_mu: float = 0.0
    ou_dt: float = 0.01
    # Where actor inference runs. Acting is latency-bound batch-E inference
    # dispatched every pool tick; on a TPU host every tick would round-trip
    # PCIe for microseconds of MLP compute, serializing
    # the env loop on transfer latency and contending with the learner's
    # dispatch queue. 'cpu' (default) pins the policy forward to the host
    # CPU backend — the D4PG production shape: the accelerator belongs to
    # the learner, actors run on TPU-VM host cores. 'default' uses the
    # default backend (worth it only for big conv encoders + wide pools).
    device: str = "cpu"  # 'cpu' | 'default'

    def __post_init__(self):
        if self.noise not in ("gaussian", "ou"):
            raise ValueError(f"unknown noise process {self.noise!r}")
        if self.device not in ("cpu", "default"):
            raise ValueError(f"unknown actor device {self.device!r}")


def resolve_act_device(kind: str):
    """Pinned inference device for an acting/eval component: the host CPU
    backend for ``'cpu'`` (see ``ActorConfig.device``), None (follow the
    default backend) for ``'default'``. Shared by actors, the serving
    plane, and the Evaluator so the placement policy lives in one place."""
    if kind not in ("cpu", "default"):
        raise ValueError(f"unknown actor device {kind!r}")
    if kind != "cpu":
        return None
    # local_devices, not devices: under jax.distributed the global device
    # list starts with process 0's devices, so devices("cpu")[0] on any
    # other process is NON-addressable and acting there either errors or
    # produces arrays this process cannot read.
    return jax.local_devices(backend="cpu")[0]


def act_device_scope(device):
    """Thread-local default-device scope for a pinned device (no-op scope
    when following the default backend)."""
    if device is None:
        return contextlib.nullcontext()
    return jax.default_device(device)


def put_params_on(device, params):
    """Move published params onto the pinned device. Publishes may carry
    accelerator arrays (the fused learner publishes device params);
    committed arrays would drag the acting computation back onto the
    learner's chip."""
    if device is None:
        return params
    return jax.device_put(params, device)


class LocalPolicyClient:
    """In-process policy queries against a ``WeightStore``-shaped handle.

    This is the policy half of the pre-serving ``_BaseActor``, moved —
    not rewritten: the jax key split order, the ``seed + 17`` numpy rng,
    the OU lazy init, and the epsilon schedule are preserved exactly so
    a seeded action stream through this client is bitwise-identical to
    the legacy actor's (the serving parity oracle pins this).
    """

    def __init__(
        self,
        config: D4PGConfig,
        actor_cfg: ActorConfig,
        weights,
        seed: int = 0,
        obs_norm=None,
    ):
        self.config = config
        self.cfg = actor_cfg
        self.weights = weights
        # READ-ONLY normalizer view for the policy input (the networks are
        # trained on standardized rows — the ReplayService's drain thread
        # owns the statistics and normalizes at insert). In-process actors
        # share the service's RunningMeanStd; remote/spawned actors receive
        # a FrozenNormalizer refreshed from the weight channel (below).
        self.obs_norm = obs_norm
        self._act_device = resolve_act_device(actor_cfg.device)
        with self._device_scope():
            self._key = jax.random.key(seed)
        self._version = 0
        self._params = None
        self._epsilon = actor_cfg.epsilon_0
        self._explore_rng = np.random.default_rng(seed + 17)
        self._episodes = 0
        self._ou = None  # lazily-sized OU state when cfg.noise == 'ou'

    def _device_scope(self):
        """Context placing this client's jax dispatches on its pinned
        device (thread-local, so actor threads don't disturb the
        learner's default placement)."""
        return act_device_scope(self._act_device)

    @property
    def epsilon(self) -> float:
        return self._epsilon

    @property
    def version(self) -> int:
        return self._version

    @property
    def params(self):
        return self._params

    def pull(self) -> bool:
        """Refresh params if the store has a newer version."""
        got = self.weights.get_if_newer(self._version)
        if got is not None:
            self._version, params = got
            self._params = put_params_on(self._act_device, params)
            # Remote/spawned actors: the weight payload piggybacks the
            # learner's normalization statistics (WeightClient.norm_stats).
            # An in-process RunningMeanStd handle stays authoritative.
            ns = getattr(self.weights, "norm_stats", None)
            if ns is not None and not isinstance(self.obs_norm, RunningMeanStd):
                if self.obs_norm is None:
                    self.obs_norm = FrozenNormalizer(*ns)
                else:
                    self.obs_norm.set(*ns)
            return True
        return False

    def snapshot_pull(self) -> tuple[int, int]:
        """Adopt the store's CURRENT params regardless of version (the
        evaluator's pull: eval must describe the weights it actually ran,
        so the published step is returned with the version)."""
        version, params, published_step = self.weights.snapshot()
        if params is None:
            raise RuntimeError("no weights published yet")
        self._version = version
        self._params = put_params_on(self._act_device, params)
        return version, published_step

    def actions(self, obs: np.ndarray) -> np.ndarray:
        """Noisy policy actions for a [B, obs_dim] batch; uniform random
        before the first weight publish (warmup, ``main.py:200-207``)."""
        with self._device_scope():
            return self._actions_inner(obs)

    def _actions_inner(self, obs: np.ndarray) -> np.ndarray:
        self._key, ka = jax.random.split(self._key)
        if self._params is None:
            return np.asarray(
                jax.random.uniform(ka, (obs.shape[0], self.config.act_dim),
                                   minval=-1.0, maxval=1.0)
            )
        if self.cfg.noise == "ou":
            if self._ou is None or self._ou.x.shape[0] != obs.shape[0]:
                self._ou = ou.init(self.config.act_dim, (obs.shape[0],))
            actions, self._ou = act_ou(
                self.config, self._params, jnp.asarray(obs), self._ou, ka,
                epsilon=self._epsilon, theta=self.cfg.ou_theta,
                mu=self.cfg.ou_mu, sigma=self.cfg.ou_sigma, dt=self.cfg.ou_dt,
            )
            actions = np.asarray(actions)
        else:
            actions = np.asarray(
                act(self.config, self._params, jnp.asarray(obs), ka,
                    self._epsilon)
            )
        if self.cfg.random_eps > 0.0:
            rng = self._explore_rng
            mask = rng.random(actions.shape[0]) < self.cfg.random_eps
            if mask.any():
                actions = np.array(actions)  # jax->np output is read-only
                actions[mask] = rng.uniform(
                    -1.0, 1.0, (int(mask.sum()), actions.shape[1])
                ).astype(actions.dtype)
        return actions

    def greedy_actions(self, obs: np.ndarray) -> np.ndarray:
        """Deterministic mu(s) for a [B, obs_dim] batch (evaluation)."""
        if self._params is None:
            raise RuntimeError("no weights pulled yet")
        with self._device_scope():
            return np.asarray(
                act_deterministic(self.config, self._params,
                                  jnp.asarray(obs))
            )

    def reset_noise(self, done_mask: np.ndarray) -> None:
        """Zero the OU state of envs whose episode ended
        (``random_process.py:41-45`` resets x on episode reset)."""
        if self._ou is not None and done_mask.any():
            with self._device_scope():  # keep the OU state on the pinned device
                keep = jnp.asarray(~done_mask, jnp.float32)[:, None]
                self._ou = self._ou._replace(x=self._ou.x * keep)

    def decay_epsilon(self) -> None:
        """eps = min + (eps0-min) * exp(-5k/horizon) on episode end — the
        decay the reference defines but never runs (``random_process.py:
        19-21``, call commented at ``main.py:366``)."""
        self._episodes += 1
        c = self.cfg
        self._epsilon = c.min_epsilon + (c.epsilon_0 - c.min_epsilon) * float(
            np.exp(-5.0 * self._episodes / c.epsilon_horizon)
        )

    def close(self) -> None:
        pass


class RemotePolicyClient:
    """Policy queries over the serving wire protocol, with a declared
    degradation ladder instead of stalls:

        1. server OK            -> served mu, local gaussian noise
        2. timeout / torn / EOF -> drop the connection (responses are
           in-order per connection; a late reply for an abandoned
           request must never be matched to a newer one), count the
           event, and fall back to
        3. cached params        -> local ``act_deterministic`` against
           the last params pulled from an optional ``weights`` handle
        4. no params anywhere   -> uniform warmup actions

    Every rung is a counted event (``stats()``); the env loop never
    blocks past ``timeout`` per tick. Exploration noise stays CLIENT
    side (the server computes greedy mu only) so one shared server
    never correlates exploration across lanes.

    Thread contract: one lane, one client (the request counter, socket,
    and rng are intentionally unshared — matching one ``EnvPool`` per
    lane thread).
    """

    def __init__(
        self,
        config: D4PGConfig,
        actor_cfg: ActorConfig,
        host: str,
        port: int,
        *,
        secret: str | None = None,
        lane_id: int = 0,
        seed: int = 0,
        timeout: float = 0.5,
        connect_timeout: float = 1.0,
        reconnect_backoff: float = 0.05,
        weights=None,
        obs_norm=None,
        trace_sample: float = 0.0,
        record_ledger: bool = False,
    ):
        if actor_cfg.noise != "gaussian":
            # OU state lives per-client; the remote split keeps noise
            # client-side but only the uncorrelated process is wired.
            raise ValueError("RemotePolicyClient supports gaussian noise only")
        self.config = config
        self.cfg = actor_cfg
        self.host, self.port = host, int(port)
        self.secret = secret
        self.lane_id = int(lane_id)
        self.weights = weights
        self.obs_norm = obs_norm
        self.timeout = float(timeout)
        self.connect_timeout = float(connect_timeout)
        self.reconnect_backoff = float(reconnect_backoff)
        self._act_device = resolve_act_device(actor_cfg.device)
        self._epsilon = actor_cfg.epsilon_0
        self._episodes = 0
        self._explore_rng = np.random.default_rng(seed + 17)
        self._noise_rng = np.random.default_rng(seed + 29)
        self._req_counter = 0
        self._sock: socket.socket | None = None
        self._next_connect = 0.0
        self._version = 0
        self._generation = 0
        self._fallback_params = None
        self._fallback_version = 0
        self._trace_sample = float(trace_sample)
        self._trace_rng = np.random.default_rng((seed << 8) ^ 0xD4E2)
        # Optional acceptance ledger for the chaos oracle: the set of
        # req_ids whose responses this client ACTED on. Intersected with
        # the server's torn-injection ledger it proves torn responses
        # are rejected, not just counted.
        self.accepted_req_ids: set[int] | None = set() if record_ledger else None
        self.stats_lock = threading.Lock()
        self._stats = {
            "requests": 0, "served": 0, "timeouts": 0, "torn_rejected": 0,
            "wire_errors": 0, "no_params": 0, "overload_rejected": 0,
            "fallbacks": 0, "warmup_fallbacks": 0, "reconnects": 0,
        }

    @property
    def epsilon(self) -> float:
        return self._epsilon

    @property
    def version(self) -> int:
        """Version of the last params that acted for this lane (server
        snapshot version, or the cached fallback's)."""
        return self._version

    @property
    def generation(self) -> int:
        return self._generation

    def _count(self, key: str, n: int = 1) -> None:
        with self.stats_lock:
            self._stats[key] += n

    def stats(self) -> dict:
        with self.stats_lock:
            return dict(self._stats)

    # -- connection ---------------------------------------------------------
    def _ensure_conn(self) -> socket.socket | None:
        from d4pg_tpu.distributed import transport

        if self._sock is not None:
            return self._sock
        now = time.monotonic()
        if now < self._next_connect:
            return None
        try:
            s = socket.create_connection((self.host, self.port),
                                         timeout=self.connect_timeout)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            transport.client_handshake(s, self.secret)
            s.settimeout(self.timeout)
            self._sock = s
            self._count("reconnects")
            return s
        except (OSError, transport.ProtocolError):
            self._next_connect = now + self.reconnect_backoff
            return None

    def _drop_conn(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    # -- weight pulls (fallback cache) --------------------------------------
    def pull(self) -> bool:
        """Refresh the local FALLBACK params (and the frozen normalizer
        view) from the optional weights handle. The server feeds itself;
        this cache only backs the degradation ladder's rung 3."""
        if self.weights is None:
            return False
        got = self.weights.get_if_newer(self._fallback_version)
        if got is None:
            return False
        self._fallback_version, params = got
        self._fallback_params = put_params_on(self._act_device, params)
        ns = getattr(self.weights, "norm_stats", None)
        if ns is not None and not isinstance(self.obs_norm, RunningMeanStd):
            if self.obs_norm is None:
                self.obs_norm = FrozenNormalizer(*ns)
            else:
                self.obs_norm.set(*ns)
        return True

    # -- the request path ---------------------------------------------------
    def _request_mu(self, obs: np.ndarray) -> np.ndarray | None:
        """One round trip; None on any failure (all counted)."""
        from d4pg_tpu.distributed.transport import _recv_exact

        sock = self._ensure_conn()
        if sock is None:
            return None
        self._req_counter += 1
        req_id = ((self.lane_id & 0xFFF) << 20) | (self._req_counter & 0xFFFFF)
        trace = None
        if self._trace_sample > 0.0 and \
                self._trace_rng.random() < self._trace_sample:
            trace = (new_trace_id(self.lane_id), time.monotonic())
        self._count("requests")
        try:
            sock.sendall(protocol.encode_request(req_id, obs, trace=trace))
            body = protocol.read_frame(sock, protocol.MAGIC_RESPONSE,
                                       _recv_exact)
            if body is None:
                raise ConnectionError("server closed")
            rsp = protocol.decode_response(body)
        except protocol.TornFrameError:
            self._count("torn_rejected")
            self._drop_conn()
            return None
        except (TimeoutError, socket.timeout):
            self._count("timeouts")
            self._drop_conn()
            return None
        except (OSError, protocol.ProtocolError, ConnectionError):
            self._count("wire_errors")
            self._drop_conn()
            return None
        if rsp["req_id"] != req_id:
            # in-order protocol: a mismatch means this connection's
            # stream no longer lines up with our requests — poison
            self._count("wire_errors")
            self._drop_conn()
            return None
        if rsp["status"] != protocol.STATUS_OK:
            # overload = the server's admission budget said no (elastic
            # plane) — same degradation rung as no-params (fall back to
            # cached params, then warmup), separate counter so a load
            # verdict never masquerades as a freshness gap
            self._count("overload_rejected"
                        if rsp["status"] == protocol.STATUS_OVERLOAD
                        else "no_params")
            return None
        self._count("served")
        self._generation = rsp["generation"]
        self._version = rsp["version"]
        if self.accepted_req_ids is not None:
            self.accepted_req_ids.add(req_id)
        return rsp["actions"]

    def _fallback_mu(self, obs: np.ndarray) -> np.ndarray | None:
        if self._fallback_params is None:
            self.pull()
        if self._fallback_params is None:
            return None
        self._count("fallbacks")
        self._version = self._fallback_version
        with act_device_scope(self._act_device):
            return np.asarray(
                act_deterministic(self.config, self._fallback_params,
                                  jnp.asarray(obs))
            )

    def actions(self, obs: np.ndarray) -> np.ndarray:
        obs = np.asarray(obs, np.float32)
        mu = self._request_mu(obs)
        if mu is None:
            mu = self._fallback_mu(obs)
        if mu is None:
            # rung 4: uniform warmup — already maximal exploration, no
            # additive noise on top
            self._count("warmup_fallbacks")
            return self._noise_rng.uniform(
                -1.0, 1.0, (obs.shape[0], self.config.act_dim)
            ).astype(np.float32)
        noise = self._noise_rng.standard_normal(mu.shape).astype(np.float32)
        actions = np.clip(mu + self._epsilon * noise, -1.0, 1.0)
        if self.cfg.random_eps > 0.0:
            rng = self._explore_rng
            mask = rng.random(actions.shape[0]) < self.cfg.random_eps
            if mask.any():
                actions[mask] = rng.uniform(
                    -1.0, 1.0, (int(mask.sum()), actions.shape[1])
                ).astype(actions.dtype)
        return actions

    def greedy_actions(self, obs: np.ndarray) -> np.ndarray:
        obs = np.asarray(obs, np.float32)
        mu = self._request_mu(obs)
        if mu is None:
            mu = self._fallback_mu(obs)
        if mu is None:
            raise RuntimeError("no server response and no cached params")
        return mu

    def reset_noise(self, done_mask: np.ndarray) -> None:
        pass  # gaussian noise is memoryless

    def decay_epsilon(self) -> None:
        self._episodes += 1
        c = self.cfg
        self._epsilon = c.min_epsilon + (c.epsilon_0 - c.min_epsilon) * float(
            np.exp(-5.0 * self._episodes / c.epsilon_horizon)
        )

    def close(self) -> None:
        self._drop_conn()
