"""Remote actor runner: ``python -m d4pg_tpu.actor_main --learner_host ...``

Runs acting on a separate host (TPU-VM actor fleet), streaming transitions
to the learner's ``TransitionReceiver`` and pulling weights from its
``WeightServer`` — the cross-host replacement for the reference's fork'd
same-host workers sharing memory (``main.py:393-405``). Actors are
stateless: kill one and start another; replay and weights live with the
learner.
"""

from __future__ import annotations

import argparse

from d4pg_tpu.config import ExperimentConfig
from d4pg_tpu.distributed.actor import (
    ActorConfig,
    ActorWorker,
    GoalActorWorker,
)
from d4pg_tpu.distributed.transport import CoalescingSender, TransitionSender
from d4pg_tpu.distributed.weight_server import WeightClient
from d4pg_tpu.envs import EnvPool
from d4pg_tpu.replay.uniform import TransitionBatch
from d4pg_tpu.train import infer_dims, make_env_fn


class RemoteReplayClient:
    """ReplayService-shaped adapter over the transition socket."""

    def __init__(self, sender: TransitionSender):
        self._sender = sender

    def add(self, batch: TransitionBatch, actor_id: str = "remote",
            block: bool = True, timeout: float | None = None,
            count_env_steps: bool = True) -> bool:
        # TCP provides ordering + backpressure. count_env_steps crosses the
        # wire as a frame flag so remote HER relabels don't inflate the
        # learner's env-step counter. Under --drop_on_timeout the sender
        # sheds timed-out frames and returns False — the actor counts the
        # loss (dropped_batches) and keeps acting instead of dying.
        del actor_id, block, timeout
        return self._sender.send(batch, count_env_steps=count_env_steps)


def run_actor(
    cfg: ExperimentConfig,
    learner_host: str,
    transitions_port: int,
    weights_port: int,
    actor_id: str = "remote-0",
    max_ticks: int | None = None,
    secret: str | None = None,
    send_timeout: float = 300.0,
    send_retries: int | None = None,
    drop_on_timeout: bool = False,
    codec: str = "npz",
    trace_sample: float = 0.0,
    expect_generation: bool = False,
    weight_codec: str | None = None,
    weight_delta: bool = True,
    policy_port: int | None = None,
    policy_timeout: float = 0.5,
) -> int:
    cfg = cfg.resolve()
    obs_dim, act_dim, obs_dtype = infer_dims(cfg)
    config = cfg.learner_config(obs_dim, act_dim)
    # Block-coalescing transport (docs/architecture.md "Ingest plane"):
    # per-tick rows ride one frame per block instead of one frame per
    # send, with backpressure-aware block sizing. Episode boundaries and
    # close() flush partial blocks. The fleet-degradation knobs
    # (--send_timeout/--send_retries/--drop_on_timeout) bound how long a
    # frame may retry and what happens at the bound: raise (default, a
    # lone actor should fail loudly) or shed-and-count (a 256-actor fleet
    # member should lose rows, not wedge).
    # --codec raw: the sharded receiver's native v2 frames — ~25x cheaper
    # to encode+decode than npz and admissible (routed/shed/counted) from
    # the fixed header alone; npz (default) interops with any receiver.
    # --trace_sample: fraction of raw frames stamped with a trace id +
    # birth timestamp (the wire-to-grad tracing plane, d4pg_tpu/obs);
    # inert at codec='npz' — only v2 headers carry the extension.
    # --expect_generation: read the service-generation greeting after the
    # handshake and stamp raw frames with it, so a learner that restarted
    # and restored a snapshot can fence pre-crash frames at admission
    # (the crash-recovery plane's exactly-once rule); requires a greeting
    # receiver (train.py serve mode always greets).
    sender = CoalescingSender(learner_host, transitions_port,
                              actor_id=actor_id, secret=secret,
                              retry_timeout=send_timeout,
                              max_retries=send_retries,
                              drop_on_timeout=drop_on_timeout,
                              codec=codec,
                              trace_sample=trace_sample,
                              expect_generation=expect_generation)
    # --weight_codec opts into the v2 weight plane (delta-encoded pulls,
    # optional bf16/int8 quantized transport, generation fencing across
    # learner restarts); the default stays the v1 full-snapshot puller —
    # the server answers both protocols on one port, per client.
    if weight_codec is not None:
        from d4pg_tpu.distributed.weight_plane import WeightPlaneClient

        weights = WeightPlaneClient(learner_host, weights_port,
                                    codec=weight_codec, delta=weight_delta,
                                    secret=secret)
    else:
        weights = WeightClient(learner_host, weights_port, secret=secret)
    actor_cfg = ActorConfig(
        epsilon_0=cfg.epsilon_0, min_epsilon=cfg.min_epsilon,
        epsilon_horizon=cfg.epsilon_horizon, n_step=cfg.n_steps,
        gamma=cfg.gamma, reward_scale=cfg.reward_scale, noise=cfg.noise,
        random_eps=cfg.random_eps, ou_theta=cfg.ou_theta,
        ou_sigma=cfg.ou_sigma, ou_mu=cfg.ou_mu, device=cfg.actor_device,
    )
    pool = None
    goal_env = None
    if cfg.her:
        # remote goal actor: whole episodes on one env, originals + HER
        # relabels streamed with the count_env_steps frame flag so the
        # learner's env-step counter stays honest
        if cfg.num_envs > 1:
            print(f"[{actor_id}] --her runs a SINGLE env per remote actor "
                  f"(episode-granular HER relabeling); ignoring "
                  f"--num_envs {cfg.num_envs}. Launch more actor processes "
                  "for width.", flush=True)
        goal_env = make_env_fn(cfg, seed=cfg.seed)()
        actor = GoalActorWorker(
            actor_id, config, actor_cfg, goal_env,
            RemoteReplayClient(sender), weights, her_ratio=cfg.her_ratio,
            rng_seed=cfg.seed, seed=cfg.seed,
        )
    else:
        pool = EnvPool(
            [make_env_fn(cfg, seed=cfg.seed + i) for i in range(cfg.num_envs)],
            seed=cfg.seed,
        )
        policy = None
        if policy_port is not None:
            # --policy_port: SEED-style serving — greedy mu comes from
            # the learner's continuous-batching PolicyInferenceServer;
            # exploration noise stays here. The weight puller above
            # still runs, but only to back the degradation ladder's
            # cached-params fallback (server down -> local mu, counted).
            import zlib as _zlib

            from d4pg_tpu.serving.client import RemotePolicyClient

            policy = RemotePolicyClient(
                config, actor_cfg, learner_host, policy_port,
                secret=secret,
                lane_id=_zlib.crc32(actor_id.encode()) & 0xFFF,
                seed=cfg.seed, timeout=policy_timeout, weights=weights)
        actor = ActorWorker(
            actor_id, config, actor_cfg, pool, RemoteReplayClient(sender),
            weights, seed=cfg.seed, obs_dtype=obs_dtype, policy=policy,
        )
    try:
        done = 0
        while max_ticks is None or done < max_ticks:
            if cfg.her:
                done += actor.run_episode(cfg.max_steps)
            else:
                chunk = 1000 if max_ticks is None else min(1000, max_ticks - done)
                actor.run(chunk)
                done += chunk
            sender.flush()  # partial blocks must not outlive the tick loop
    except (KeyboardInterrupt, ConnectionError, BrokenPipeError, OSError) as e:
        print(f"actor {actor_id} stopping: {type(e).__name__}: {e}")
    finally:
        if sender.frames_dropped or actor.dropped_batches:
            # shed rows are benign but NEVER silent (fleet-plane rule)
            print(f"actor {actor_id} shed {sender.frames_dropped} frames "
                  f"({sender.retries} transport retries) under backpressure",
                  flush=True)
        sender.close()
        weights.close()
        if pool is not None:
            pool.close()
        if goal_env is not None and hasattr(goal_env, "close"):
            goal_env.close()
    return actor.env_steps


def run_local_actor_process(
    cfg: ExperimentConfig,
    learner_host: str,
    transitions_port: int,
    weights_port: int,
    actor_id: str,
    secret: str | None = None,
    expect_generation: bool = False,
) -> None:
    """Entry point for locally SPAWNED actor processes (``train.py
    --actor_procs N`` — the proper replacement for the reference's
    ``mp.Process`` fan-out, ``main.py:399-405``, which shared memory and
    the GIL-free illusion; these are real processes talking TCP).

    Pins the CPU backend first: one process holds a chip at a time and
    it belongs to the learner; actor inference on these MLPs is
    host-friendly. The pin works because importing this module (and
    everything it imports) initialises no backend — pinned by
    ``tests/test_startup.py``.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    try:
        run_actor(cfg, learner_host, transitions_port, weights_port,
                  actor_id=actor_id, secret=secret,
                  expect_generation=expect_generation)
    except KeyboardInterrupt:
        pass


def main(argv=None):
    p = argparse.ArgumentParser(prog="d4pg_tpu.actor_main")
    p.add_argument("--learner_host", required=True)
    p.add_argument("--transitions_port", type=int, required=True)
    p.add_argument("--weights_port", type=int, required=True)
    p.add_argument("--actor_id", default="remote-0")
    p.add_argument("--env", default="Pendulum-v1")
    p.add_argument("--num_envs", type=int, default=4,
                   help="vectorized env pool width; with --her 1 the remote "
                        "actor always runs a single env (launch more actor "
                        "processes for width)")
    p.add_argument("--n_steps", type=int, default=None,
                   help="n-step horizon (default: from the env preset)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--noise", choices=("gaussian", "ou"), default="gaussian")
    p.add_argument("--random_eps", type=float, default=0.0)
    p.add_argument("--her", type=int, choices=(0, 1), default=0)
    p.add_argument("--her_ratio", type=float, default=0.8)
    p.add_argument("--max_steps", type=int, default=None,
                   help="episode horizon (default: from the env preset)")
    p.add_argument("--max_ticks", type=int, default=None)
    p.add_argument("--secret", default="",
                   help="shared secret matching the learner's --serve_secret")
    p.add_argument("--actor_device", choices=("cpu", "default"), default="cpu")
    p.add_argument("--send_timeout", type=float, default=300.0,
                   help="seconds a frame may retry across reconnects")
    p.add_argument("--send_retries", type=int, default=None,
                   help="max reconnect attempts per frame (default: "
                        "unbounded within --send_timeout)")
    p.add_argument("--drop_on_timeout", type=int, choices=(0, 1), default=0,
                   help="1: shed timed-out frames (counted) and keep "
                        "acting — the fleet-member policy; 0: raise and "
                        "stop (default)")
    p.add_argument("--codec", choices=("npz", "raw"), default="npz",
                   help="wire frame format: npz (legacy, self-describing) "
                        "or raw (v2 column frames — the sharded receiver's "
                        "native format, ~25x cheaper per frame)")
    p.add_argument("--trace_sample", type=float, default=0.0,
                   help="fraction of frames stamped with a wire-to-grad "
                        "trace id + birth timestamp in the v2 header "
                        "extension (requires --codec raw; the learner "
                        "aggregates per-stage latency histograms)")
    p.add_argument("--expect_generation", type=int, choices=(0, 1), default=0,
                   help="1: read the learner's service-generation greeting "
                        "on connect and stamp raw frames with it, so a "
                        "restarted learner fences pre-crash frames instead "
                        "of double-inserting them (requires a greeting "
                        "learner, e.g. train.py serve mode)")
    p.add_argument("--weight_codec", choices=("f32", "bf16", "int8"),
                   default=None,
                   help="opt into the v2 weight plane with this transport "
                        "codec: f32 (full precision), bf16 (2x smaller, "
                        "rel err <= 2^-8) or int8 (4x smaller, per-tensor "
                        "scale); default: the v1 full-snapshot puller")
    p.add_argument("--policy_port", type=int, default=None,
                   help="query greedy actions from the learner's "
                        "continuous-batching policy server on this port "
                        "(train.py --serve_policy) instead of acting "
                        "locally; on timeout/corruption the actor degrades "
                        "to its cached weights — counted, never a stall "
                        "(gaussian noise only)")
    p.add_argument("--policy_timeout", type=float, default=0.5,
                   help="per-request serving timeout (s) before the "
                        "cached-params fallback")
    p.add_argument("--weight_delta", type=int, choices=(0, 1), default=1,
                   help="with --weight_codec: 1 (default) pulls per-tensor "
                        "deltas against the last accepted version when the "
                        "server still holds it in its window; 0 always "
                        "pulls full frames")
    ns = p.parse_args(argv)
    if ns.actor_device == "cpu":
        # Acting runs on host CPU; pin the platform BEFORE any jax call:
        # one process holds a chip at a time, and an actor that merely
        # enumerated the TPU would take it from the learner on this host.
        import jax

        jax.config.update("jax_platforms", "cpu")
    cfg = ExperimentConfig(
        env=ns.env, num_envs=ns.num_envs, n_steps=ns.n_steps,
        max_steps=ns.max_steps, seed=ns.seed, noise=ns.noise,
        random_eps=ns.random_eps, her=bool(ns.her), her_ratio=ns.her_ratio,
        actor_device=ns.actor_device)
    steps = run_actor(cfg, ns.learner_host, ns.transitions_port,
                      ns.weights_port, ns.actor_id, ns.max_ticks,
                      secret=ns.secret or None,
                      send_timeout=ns.send_timeout,
                      send_retries=ns.send_retries,
                      drop_on_timeout=bool(ns.drop_on_timeout),
                      codec=ns.codec, trace_sample=ns.trace_sample,
                      expect_generation=bool(ns.expect_generation),
                      weight_codec=ns.weight_codec,
                      weight_delta=bool(ns.weight_delta),
                      policy_port=ns.policy_port,
                      policy_timeout=ns.policy_timeout)
    print(f"collected {steps} env steps")


if __name__ == "__main__":
    main()
