"""Training driver: ``python -m d4pg_tpu.train --env Pendulum-v1 ...``

Parity: the reference's ``main.py`` orchestration (SURVEY.md S1/C15): the
HER-paper-shaped loop — epochs x cycles x (collect episodes + train steps)
with per-cycle eval, TensorBoard logging and checkpointing
(``main.py:299-368``) — rebuilt around the decoupled TPU runtime:

  - actors collect into the central ``ReplayService`` (vectorized pool,
    batched jit inference) instead of per-process buffers;
  - the learner runs the single jit'd (optionally mesh-sharded) update;
  - weights flow learner -> actors via the versioned ``WeightStore``
    instead of shared-memory state_dict pulls;
  - checkpoints are full-state Orbax saves with ``--resume 1`` restore
    (the reference can only save, ``main.py:367-368``).
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from d4pg_tpu.config import ExperimentConfig, parse_args
from d4pg_tpu.distributed import (
    ActorConfig,
    ActorWorker,
    AsyncEvaluator,
    Evaluator,
    ReplayService,
    WeightStore,
)
from d4pg_tpu.distributed.actor import GoalActorWorker
from d4pg_tpu.envs import (
    EnvPool,
    FakeGoalEnv,
    PixelPointEnv,
    PointMassEnv,
    get_preset,
)
from d4pg_tpu.io import CheckpointManager, CsvLogger, MetricsBus, TensorBoardSink
from d4pg_tpu.obs import startup_log
from d4pg_tpu.obs.containment import contained_crash
from d4pg_tpu.io.profiling import RecompileSentinel, StepTimer, xla_trace
from d4pg_tpu.learner import (
    init_state,
    make_multi_update,
    make_update,
    policy_params,
)
from d4pg_tpu.learner.loop import FusedLoop
from d4pg_tpu.learner.pipeline import ChunkPipeline
from d4pg_tpu.parallel import (
    MeshSpec,
    make_mesh,
    replicate_state,
    shard_batch,
    stacked_sharding,
)
from d4pg_tpu.parallel.mesh import DATA_AXIS
from d4pg_tpu.replay import LinearSchedule, PrioritizedReplayBuffer, ReplayBuffer
from d4pg_tpu.replay import device_per as dper
from d4pg_tpu.replay.uniform import TransitionBatch


def make_env_fn(cfg: ExperimentConfig, seed: int):
    """Build one env instance (``_make_env_fn``); with ``--torso`` its
    observations are the history the torso reads (envs/wrappers.History)."""
    make = _make_env_fn(cfg, seed)
    block = cfg.torso_block()
    if block is None:
        return make
    if cfg.her:
        raise ValueError("--torso reads flat state vectors; --her envs have "
                         "dict observations")
    from d4pg_tpu.envs.wrappers import History

    return lambda: History(make(), int(block["tokens"]))


def _make_env_fn(cfg: ExperimentConfig, seed: int):
    """gymnasium by id, with fake-env fallbacks for ids 'point' and
    'fake-goal' (tests/smoke, SURVEY.md §4)."""
    if ((cfg.env in ("point", "fake-goal")
         or cfg.env.startswith("point-slow:")) and cfg.frame_stack > 1):
        # fail loudly rather than silently training on unstacked frames —
        # the exact POMDP failure the flag exists to fix
        raise ValueError(
            f"--frame_stack {cfg.frame_stack} requires a pixel env; "
            f"{cfg.env!r} is state-observation")
    if cfg.env == "point":
        return lambda: PointMassEnv(horizon=cfg.max_steps, seed=seed)
    if cfg.env.startswith("point-slow:"):
        # 'point-slow:<ms>' — point mass with a fixed <ms> wall cost per
        # step, emulating a physics-bound env for transport-plane scaling
        # measurements (analysis/actor_scaling.py) without MuJoCo
        from d4pg_tpu.envs.fake import SlowEnv

        step_ms = float(cfg.env.split(":", 1)[1])
        return lambda: SlowEnv(PointMassEnv(horizon=cfg.max_steps, seed=seed),
                               step_ms / 1e3)
    if cfg.env == "fake-goal":
        return lambda: FakeGoalEnv(horizon=cfg.max_steps, seed=seed)
    def stack(make_pixel_env):
        # FrameStack restores the Markov property for pixel control
        # (single frames hide velocities); no-op at the default k=1
        if cfg.frame_stack <= 1:
            return make_pixel_env
        from d4pg_tpu.envs.wrappers import FrameStack

        return lambda: FrameStack(make_pixel_env(), cfg.frame_stack)

    if cfg.env == "pixel-point":
        return stack(lambda: PixelPointEnv(horizon=cfg.max_steps, seed=seed))
    from d4pg_tpu.envs.dmc import DMControlEnv, parse_dmc_id

    dmc = parse_dmc_id(cfg.env)
    if dmc is not None:
        domain, task, pixels = dmc
        if not pixels and cfg.frame_stack > 1:
            raise ValueError(
                f"--frame_stack {cfg.frame_stack} requires a pixel env; "
                f"{cfg.env!r} is state-observation")
        mk = lambda: DMControlEnv(domain, task, pixels=pixels, seed=seed,
                                  height=cfg.pixel_size,
                                  width=cfg.pixel_size)
        return stack(mk) if pixels else mk
    import gymnasium as gym

    def make():
        try:
            env = gym.make(cfg.env)
        except (gym.error.NameNotFound, gym.error.VersionNotFound):
            # Fetch/Adroit/Shadow-Hand live in gymnasium_robotics, which
            # registers its ids only once imported (BASELINE.md config #5).
            # Their MuJoCo-2-era MJCF needs the apirate compat shim to load
            # under MuJoCo 3 (envs/robotics_compat.py).
            import gymnasium_robotics

            from d4pg_tpu.envs.robotics_compat import install

            install()
            gym.register_envs(gymnasium_robotics)
            env = gym.make(cfg.env)
        if cfg.frame_stack > 1:
            # stack 3-D (pixel) observations; anything else is a config
            # error — silently dropping the flag would train on single
            # frames, the exact POMDP failure it exists to fix
            if len(env.observation_space.shape or ()) != 3:
                raise ValueError(
                    f"--frame_stack {cfg.frame_stack} requires pixel "
                    f"[H, W, C] observations; {cfg.env!r} has shape "
                    f"{env.observation_space.shape}")
            from d4pg_tpu.envs.wrappers import FrameStack

            return FrameStack(env, cfg.frame_stack)
        return env

    return make


def infer_dims(cfg: ExperimentConfig) -> tuple[int | tuple, int, np.dtype]:
    """obs spec, act dim, and obs storage dtype; goal-concatenated for HER
    envs (``main.py:73-80``), an [H, W, C] shape tuple for pixel envs. The
    dtype comes from an actual reset observation — rank alone must not
    decide it (a float-valued 3-D obs stored as uint8 would be silently
    truncated to garbage)."""
    env = make_env_fn(cfg, seed=0)()
    try:
        shape = env.observation_space.shape
        obs_dtype = np.dtype(np.float32)
        if cfg.her:
            obs, _ = env.reset(seed=0)
            obs_dim = obs["observation"].shape[-1] + obs["desired_goal"].shape[-1]
        elif len(shape) == 3:  # pixels
            obs_dim = tuple(shape)
            obs, _ = env.reset(seed=0)
            obs_dtype = np.asarray(obs).dtype
            if np.issubdtype(obs_dtype, np.floating):
                obs_dtype = np.dtype(np.float32)
        else:
            obs_dim = int(np.prod(shape))
        act_dim = int(np.prod(env.action_space.shape))
    finally:
        env.close()
    return obs_dim, act_dim, obs_dtype


def _host_replay_path(run_dir: str, process_index: int) -> str:
    from d4pg_tpu.io.checkpoint import replay_sidecar_path

    return replay_sidecar_path(run_dir, process_index)


def _save_host_replay(run_dir: str, process_index: int, step: int,
                      snap: dict) -> None:
    """Sidecar replay snapshot — EVERY host's, process 0 included (round
    4: replay used to ride the Orbax ``extra`` payload on process 0, but
    that couples replay availability to the checkpoint retention window —
    with a coarser ``--checkpoint_replay_every`` cadence the LATEST state
    checkpoint usually lacks the payload and resume silently restarted
    with an empty buffer). Stamped with the learner step it was taken at.
    The io-layer writer (``io/checkpoint.save_replay_sidecar``) does the
    write-then-rename AND frames the pickle with a CRC, so a crash
    mid-save leaves the previous snapshot intact and a torn file is
    rejected cleanly at load instead of half-restoring."""
    from d4pg_tpu.io.checkpoint import save_replay_sidecar

    save_replay_sidecar(run_dir, process_index, step, snap)


def _load_host_replay(run_dir: str, process_index: int,
                      step: int) -> tuple[dict | None, int]:
    """Load this host's replay sidecar; returns ``(snap, snap_step)``
    (``(None, -1)`` when absent/refused). A snapshot OLDER than the
    restored state is accepted with a warning — stale rows are still
    valid experience, and an almost-full slightly-stale buffer resumes
    far better than an empty one (the strict-equality rule this replaces
    emptied the buffer whenever the replay cadence was coarser than the
    state cadence). A snapshot NEWER than the state is refused: the save
    site commits the state checkpoint BEFORE renaming the sidecar, so
    ahead-of-state means mixed-up run dirs or a rolled-back checkpoint.
    A CORRUPT sidecar (CRC/format failure) is refused the same way, with
    the io layer's diagnostic — learner-only resume beats poisoning the
    buffer with a torn snapshot. Multi-host fused restores additionally
    require the snapshot step to AGREE across hosts (see the resume
    site) — per-host staleness is fine for independent host buffers, but
    the sharded device buffer is one logical store whose shard-sets must
    come from one save moment."""
    from d4pg_tpu.io.checkpoint import (SnapshotCorruptError,
                                        load_replay_sidecar)

    try:
        loaded = load_replay_sidecar(run_dir, process_index)
    except SnapshotCorruptError as e:
        print(f"[p{process_index}] replay sidecar is corrupt ({e}); "
              "refusing it — resuming learner-only with an empty buffer",
              flush=True)
        return None, -1
    if loaded is None:
        return None, -1
    snap, snap_step = loaded
    if snap_step > int(step):
        print(f"[p{process_index}] replay sidecar is from step "
              f"{snap_step}, AHEAD of the restored state at step {step}; "
              "refusing it (mixed run dirs?) — starting with an empty "
              "buffer", flush=True)
        return None, -1
    if snap_step < int(step):
        print(f"[p{process_index}] replay sidecar is from step "
              f"{snap_step} ({int(step) - snap_step} steps behind the "
              "restored state); resuming with the slightly-stale buffer",
              flush=True)
    return snap, snap_step


def _restore_replay(service, snap: dict, env_steps: int) -> None:
    """Land a sidecar snapshot in the service. A SERVICE-level snapshot
    (crash-recovery plane: buffer cut + ticket floor + generation) goes
    through ``ReplayService.restore`` — which also bumps the generation
    so pre-crash raw frames fence at admission; a legacy buffer-only
    dict keeps the old ``load_replay_state`` path. The env-step counter
    stays with the CHECKPOINT's value either way: a stale sidecar must
    not roll the interaction ledger back below the restored state's."""
    if isinstance(snap, dict) and "buffer" in snap:
        service.restore(snap)
        service.set_env_steps(env_steps)
    else:
        service.load_replay_state(snap)


def _platforms(x: jax.Array) -> str:
    """The platform(s) holding ``x``, e.g. ``'tpu'``."""
    return ",".join(sorted({d.platform for d in x.devices()}))


def _layouts(formats) -> str:
    """The layout each ring field is pinned to, major to minor:
    ``obs:01,action:-,...`` (``01``: a row's values along the lanes, what
    ``replay/device_ring.ring_layout`` pins a wide float field to;
    ``0312``: a frame's channels as planes with W along the lanes, what it
    pins ``uint8`` frames to on a TPU; ``-``: left to the compiler)."""
    return ",".join(
        name + ":" + ("-" if fmt is None else
                      "".join(map(str, fmt.layout.major_to_minor)))
        for name, fmt in zip(formats._fields, formats))


def train(cfg: ExperimentConfig) -> dict:
    cfg = cfg.resolve()
    # Multi-host SPMD (parallel/multihost.py): every host runs this same
    # function with identical flags; host-side work (replay, actors) is
    # per-host, device work spans the global mesh. Process 0 owns io/eval.
    multi_host = jax.process_count() > 1
    is_main = jax.process_index() == 0
    run_dir = os.path.join(cfg.log_dir, cfg.run_name())
    # every process may write here (multi-host hosts > 0 put their replay
    # sidecar snapshots in the run dir)
    os.makedirs(run_dir, exist_ok=True)

    obs_dim, act_dim, obs_dtype = infer_dims(cfg)
    config = cfg.learner_config(obs_dim, act_dim)

    # --- learner state (placed three ways) + update (one builder) ---------
    mesh = None
    if multi_host:
        from functools import partial

        from d4pg_tpu.parallel import multihost

        mesh = multihost.global_mesh()
        # identical seed on every host -> identical replicated state;
        # constructed inside jit because host device_put cannot address
        # other hosts' devices
        state = multihost.replicate_state_global(
            partial(init_state, config, jax.random.key(cfg.seed)), mesh)
    elif cfg.data_parallel > 1:
        mesh = make_mesh(MeshSpec(data_parallel=cfg.data_parallel),
                         devices=jax.devices()[:cfg.data_parallel])
        state = replicate_state(init_state(config, jax.random.key(cfg.seed)),
                                mesh)
    else:
        state = init_state(config, jax.random.key(cfg.seed))
    update = make_update(config, mesh=mesh, donate=True)

    # --- replay + schedule ------------------------------------------------
    storage = cfg.replay_storage
    if storage == "auto":
        # Device-resident ring when an accelerator is attached:
        # per-dispatch H2D drops from O(batch bytes) to O(indices) —
        # single device (replay/device_ring.py) or sharded over the mesh's
        # data axis (replay/sharded_per.py). Multi-host keeps rows on the
        # host (per-host replay shards); fall back when the ring wouldn't
        # fit comfortably in HBM.
        obs_elems = int(np.prod(obs_dim)) if not np.isscalar(obs_dim) else obs_dim
        ring_bytes = cfg.memory_size * (
            2 * obs_elems * np.dtype(obs_dtype).itemsize + (act_dim + 3) * 4)
        # the ring shards over the mesh's data axis, so the HBM budget is
        # per-shard, not whole-ring
        n_ring_shards = int(mesh.shape[DATA_AXIS]) if mesh is not None else 1
        storage = (
            "device"
            if jax.default_backend() != "cpu"
            and ring_bytes / n_ring_shards < 8e9
            # a sharded (mesh) learner — and ANY multi-host learner — can
            # only use device storage through the fused path; 'auto' must
            # resolve to host, not raise, when that path is disabled
            and (cfg.fused_replay != "off"
                 or (cfg.data_parallel == 1 and not multi_host))
            else "host"
        )
    elif storage == "device" and multi_host and cfg.fused_replay == "off":
        raise ValueError(
            "--replay_storage device on the multi-host runtime requires "
            "the fused replay path (--fused_replay auto/on); with it "
            "disabled, per-host replay shards stay in host RAM — use "
            "'host' or 'auto'")
    # Fully-fused replay+learn path (learner/fused.py): the PER trees join
    # the ring in HBM and the whole per-step replay protocol runs inside
    # the scanned dispatch — zero per-chunk host round trips, zero priority
    # staleness (at K=1 this IS the reference's exact per-step write-back,
    # ddpg.py:252-255, executed on device). With a mesh the ring and trees
    # shard over the data axis (each device samples its own B/N rows);
    # multi-host, each host owns its local devices' shards and drains its
    # own actors' rows into them (replay/sharded_per.py).
    fused = cfg.fused_replay != "off" and storage == "device"
    if cfg.fused_replay == "on" and not fused:
        raise ValueError(
            "--fused_replay on requires device replay storage "
            f"(storage resolved to {storage!r})")
    if storage == "device" and not fused:
        # the non-fused device ring lives on ONE device; a sharded learner
        # would re-pay the cross-device copy every dispatch
        if mesh is not None:
            raise ValueError(
                "--replay_storage device with --data_parallel > 1 requires "
                "the fused path (--fused_replay auto/on)")
    # Sample-path arm for --sample_on_ingest: resolved BEFORE buffer
    # construction because the device arm ('scan') changes what the
    # service owns — a gen-tracked fused device ring whose commit thread
    # runs the stratified descent fused behind the commit dispatch, dealing
    # device-resident blocks. 'host' keeps the PR-12 host SampleDealer
    # against host replay storage.
    dealt_arm = None
    if cfg.sample_on_ingest and cfg.prioritized_replay:
        from d4pg_tpu.replay.device_sampler import resolve_sampler

        dealt_arm = resolve_sampler(cfg.sampler)
        if dealt_arm == "scan":
            if mesh is not None or multi_host:
                raise ValueError(
                    "--sampler scan (device-dealt) makes the commit "
                    "thread the single owner of every device handle — "
                    "mesh/multi-host learners need --sampler host")
            if cfg.ingest_shards != 1:
                raise ValueError(
                    "--sampler scan needs --ingest_shards 1: the "
                    "gen-tracked ring pre-assigns slots under ONE commit "
                    "thread (shard it with --sampler host instead)")
            if cfg.fused_replay == "on":
                raise ValueError(
                    "--fused_replay on (the FusedLoop learner) conflicts "
                    "with --sample_on_ingest: the device-dealt arm owns "
                    "the commit dispatch itself — drop --fused_replay on")
            # The learner-side fused path is OFF (replicas consume dealt
            # blocks); the service's buffer is still a fused device ring,
            # built gen-tracked below.
            fused = False
    if fused and mesh is not None:
        from d4pg_tpu.replay.sharded_per import ShardedFusedReplay

        n_data = int(mesh.shape[DATA_AXIS])
        if cfg.batch_size % n_data:
            # fail at startup, not after a whole warmup of rollouts
            raise ValueError(
                f"--bsize {cfg.batch_size} must divide by the mesh's data "
                f"axis ({n_data}) for the sharded fused replay path")
        buffer = ShardedFusedReplay(cfg.memory_size, obs_dim, act_dim, mesh,
                                    alpha=cfg.per_alpha,
                                    prioritized=cfg.prioritized_replay,
                                    obs_dtype=obs_dtype)
    elif fused:
        from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay

        # ingest_shards must match the service's K: the shard workers
        # direct-stage into per-shard rings, so a lone ring would get K
        # pushers with interleaved tickets (merge assumes per-ring
        # ticket-ascending) — ReplayService.__init__ asserts agreement
        buffer = FusedDeviceReplay(cfg.memory_size, obs_dim, act_dim,
                                   alpha=cfg.per_alpha,
                                   prioritized=cfg.prioritized_replay,
                                   obs_dtype=obs_dtype,
                                   ingest_shards=cfg.ingest_shards)
    elif dealt_arm == "scan":
        from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay

        # the device-dealt service buffer: slots pre-assigned on the
        # host, priorities/generations committed by the ONE jitted
        # dispatch, sampled on device by the attached DeviceSampleDealer
        buffer = FusedDeviceReplay(cfg.memory_size, obs_dim, act_dim,
                                   alpha=cfg.per_alpha, prioritized=True,
                                   obs_dtype=obs_dtype, ingest_shards=1,
                                   gen_tracked=True)
    elif cfg.prioritized_replay:
        buffer = PrioritizedReplayBuffer(cfg.memory_size, obs_dim, act_dim,
                                         alpha=cfg.per_alpha, seed=cfg.seed,
                                         obs_dtype=obs_dtype, storage=storage)
    else:
        buffer = ReplayBuffer(cfg.memory_size, obs_dim, act_dim, seed=cfg.seed,
                              obs_dtype=obs_dtype, storage=storage)
    # The resolved plan, printed unconditionally: every 'auto' above is a
    # decision the run log must name (a device ring that silently resolved
    # to host is a different program).
    plan = {
        "platform": jax.default_backend(),
        "storage": storage,
        "fused": fused,
        "K": max(1, cfg.updates_per_dispatch),
        "devices": ([d.id for d in mesh.devices.flat]
                    if mesh is not None else [jax.devices()[0].id]),
        # where the learner state (and, fused, the replay ring) live
        "state_on": _platforms(state.step),
    }
    if fused:
        plan["ring_on"] = _platforms(buffer.storage.obs)
        if hasattr(buffer, "formats"):  # the one-device ring
            plan["ring_layout"] = _layouts(buffer.formats)
        if hasattr(buffer, "block_rows") and buffer.trees is not None:
            # how set_leaves repairs the trees at the chunk's and the
            # commit's batch (static per shape; replay/device_per.py)
            cap = buffer.trees.capacity
            plan["tree_repair"] = (
                f"chunk:{dper.plan_text(cap, cfg.batch_size)};"
                f"commit:{dper.plan_text(cap, buffer.block_rows)}")
    if isinstance(buffer, PrioritizedReplayBuffer):
        # the only buffer with a host tree (the fused path has none):
        # name the backend that loaded — the C++ library is built on
        # demand and falls back to numpy when `make` fails
        plan["host_tree"] = buffer.tree_backend
    print("plan: " + " ".join(f"{k}={v}" for k, v in plan.items()),
          flush=True)
    beta = LinearSchedule(cfg.per_beta_steps, 1.0, cfg.per_beta0)
    # Observation normalization lives with the replay service (single
    # writer: its drain thread folds every ingested row into the stats and
    # inserts normalized); actors/eval hold read-only views, remote actors
    # get (mean, std) over the weight channel.
    obs_norm = None
    if cfg.normalize_obs:
        if config.pixels:
            raise ValueError("--normalize_obs is for vector observations; "
                             "the pixel encoder already normalizes by /255")
        if multi_host:
            # per-host stats would normalize each host's replay rows
            # differently under globally-shared params; the synced variant
            # allgather-merges per-cycle deltas so every host standardizes
            # with identical statistics (HER paper's MPI-averaged stats)
            from d4pg_tpu.envs.normalizer import SyncedRunningMeanStd

            obs_norm = SyncedRunningMeanStd(config.obs_dim,
                                            clip=cfg.normalize_clip)
        else:
            from d4pg_tpu.envs.normalizer import RunningMeanStd

            obs_norm = RunningMeanStd(config.obs_dim, clip=cfg.normalize_clip)
    service = ReplayService(buffer, obs_norm=obs_norm,
                            num_ingest_shards=cfg.ingest_shards)

    # --- io (process 0 owns all of it in multi-host mode) ----------------
    bus = MetricsBus(echo=is_main)
    ckpt = None
    if is_main:
        try:
            bus.add_sink(TensorBoardSink(run_dir))
        except Exception as e:  # tensorboard optional at runtime
            print(f"tensorboard disabled: {e}")
        # first two data columns keep the reference's offline-plot shape
        # (plots/plots.py:29-37 reads step,avg,curr); success_rate rides as
        # a third column for the sparse-reward/HER evidence plots
        bus.add_sink(CsvLogger(
            os.path.join(run_dir, "returns.csv"),
            ["avg_test_reward", "ewma_test_reward", "success_rate"]))
        ckpt = CheckpointManager(
            os.path.join(run_dir, "ckpt"),
            active_processes={0} if multi_host else None)
    extra: dict = {"env_steps": 0}
    if cfg.resume and multi_host:
        # Restore on process 0, broadcast, re-replicate over the global
        # mesh; every host then loads ITS OWN replay shard snapshot
        # (process 0's rides the Orbax extra payload, hosts > 0 write
        # sidecar files — see the save site below).
        from jax.experimental import multihost_utils

        def _state_raw(s):
            # typed PRNG keys don't cross the allgather; carry raw key data
            d = s._asdict()
            d["key"] = jax.random.key_data(d["key"])
            return jax.tree_util.tree_map(np.asarray, d)

        host_state = jax.device_get(state)  # replicated -> host template
        if is_main and ckpt is not None and ckpt.latest_step is not None:
            restored, extra = ckpt.restore(host_state)
            raw, found = _state_raw(restored), 1
        else:
            raw, found = _state_raw(host_state), 0
        found = int(multihost_utils.broadcast_one_to_all(np.int32(found)))
        if found:
            raw = multihost_utils.broadcast_one_to_all(raw)

            def _rebuild():
                d = {k: jax.tree_util.tree_map(jnp.asarray, v)
                     for k, v in raw.items()}
                d["key"] = jax.random.wrap_key_data(jnp.asarray(raw["key"]))
                from d4pg_tpu.learner.state import D4PGState

                return D4PGState(**d)

            state = multihost.replicate_state_global(_rebuild, mesh)
            env_steps = int(multihost_utils.broadcast_one_to_all(
                np.int64(extra.get("env_steps", 0))))
            extra["env_steps"] = env_steps
            service.set_env_steps(env_steps)
            # normalize-flag agreement must be decided identically on ALL
            # hosts before any further collective: a process-0-only raise
            # would leave the other hosts hung in the next barrier
            has_norm = int(multihost_utils.broadcast_one_to_all(
                np.int32(1 if extra.get("obs_norm") else 0)))
            if has_norm and obs_norm is None:
                raise ValueError(
                    "checkpoint was trained with --normalize_obs (its "
                    "policy and replay rows live in normalized space); "
                    "resume with the flag")
            if obs_norm is not None:
                if not has_norm and env_steps > 0:
                    raise ValueError(
                        "--normalize_obs resume from a checkpoint without "
                        "obs_norm statistics: the restored policy/replay "
                        "are in raw space — resume without the flag, or "
                        "restart training")
                if has_norm:
                    # fixed-shape stats payload -> identical estimators
                    d = (extra.get("obs_norm")
                         or {"count": 0.0,
                             "mean": np.zeros(config.obs_dim),
                             "m2": np.zeros(config.obs_dim),
                             "clip": cfg.normalize_clip, "eps": 1e-2})
                    payload = np.concatenate(
                        [[d["count"]], d["mean"], d["m2"],
                         [d["clip"], d["eps"]]]).astype(np.float64)
                    payload = np.asarray(
                        multihost_utils.broadcast_one_to_all(payload))
                    n = config.obs_dim
                    extra["obs_norm"] = {
                        "count": float(payload[0]), "mean": payload[1:1 + n],
                        "m2": payload[1 + n:1 + 2 * n],
                        "clip": float(payload[-2]), "eps": float(payload[-1]),
                    }
            restored_step = int(np.asarray(raw["step"]))
            # every host restores from its sidecar; a legacy checkpoint
            # may still carry process 0's buffer in the Orbax extra
            # (saved atomically with the state, so its step IS the state's)
            snap, snap_step = (extra.pop("replay", None), restored_step) \
                if is_main and extra.get("replay") else (None, -1)
            if snap is None:
                snap, snap_step = _load_host_replay(
                    run_dir, jax.process_index(), restored_step)
            if fused:
                # The sharded fused restore is COLLECTIVE downstream (the
                # next drain allgathers), and the device buffer is ONE
                # logical store: every host's shard-set must come from
                # the SAME save moment. Agree on the snapshot step — a
                # host that crashed between its peers' sidecar renames
                # holds an older one, and loading mixed-step shard-sets
                # would silently mix replay timelines (rows, priorities,
                # size counters) within one buffer. On any mismatch or
                # missing snapshot, ALL hosts restart with empty replay.
                steps_all = multihost_utils.process_allgather(
                    np.int64(snap_step))
                agreed = (int(steps_all.min()) == int(steps_all.max())
                          and int(steps_all.min()) >= 0)
                if agreed:
                    _restore_replay(service, snap, env_steps)
                elif snap is not None:
                    print(f"[p{jax.process_index()}] replay sidecar steps "
                          f"disagree across hosts ({steps_all.tolist()}); "
                          "all hosts restart with empty replay", flush=True)
            elif snap is not None:
                _restore_replay(service, snap, env_steps)
            print(f"[p{jax.process_index()}] resumed from step "
                  f"{int(jax.device_get(state.step))} ({service.env_steps} "
                  f"env steps, {len(service)} replay rows)", flush=True)
    elif cfg.resume and ckpt is not None and ckpt.latest_step is not None:
        state, extra = ckpt.restore(state if mesh is None else jax.device_get(state))
        if mesh is not None:
            state = replicate_state(state, mesh)
        service.set_env_steps(extra.get("env_steps", 0))
        # elastic recovery: buffer contents + PER priorities (resumed
        # learners otherwise retrain from an empty buffer). Legacy
        # checkpoints carry the buffer in the Orbax extra; current runs
        # write the step-stamped sidecar (stale-tolerant — see
        # _load_host_replay).
        snap = extra.pop("replay", None)
        if snap is None:
            snap, _ = _load_host_replay(run_dir, 0, int(state.step))
        if snap:
            _restore_replay(service, snap, extra.get("env_steps", 0))
        print(f"resumed from step {int(state.step)} "
              f"({service.env_steps} env steps, "
              f"{len(service)} replay rows)")

    # --- actors + evaluator ----------------------------------------------
    if obs_norm is not None:
        if extra.get("obs_norm"):
            # resume with the statistics the stored replay rows (and the
            # restored policy) were normalized with
            obs_norm.load_state_dict(extra.pop("obs_norm"))
        elif cfg.resume and extra.get("env_steps"):
            raise ValueError(
                "--normalize_obs resume from a checkpoint without obs_norm "
                "statistics: the restored policy/replay are in raw space — "
                "resume without the flag, or restart training")
    elif extra.get("obs_norm"):
        raise ValueError(
            "checkpoint was trained with --normalize_obs (its policy and "
            "replay rows live in normalized space); resume with the flag")
    weights = WeightStore()

    def _norm_snapshot():
        # (mean, std, clip): clip travels with the stats so remote actors
        # standardize policy inputs bitwise-identically to the replay rows
        # even under a non-default --normalize_clip.
        return ((*obs_norm.stats(), obs_norm.clip)
                if obs_norm is not None else None)

    weights.publish(
        policy_params(config, state) if mesh is None else jax.device_get(policy_params(config, state)),
        step=int(jax.device_get(state.step)),
        norm_stats=_norm_snapshot(),
    )
    actor_cfg = ActorConfig(
        epsilon_0=cfg.epsilon_0, min_epsilon=cfg.min_epsilon,
        epsilon_horizon=cfg.epsilon_horizon, n_step=cfg.n_steps,
        gamma=cfg.gamma, reward_scale=cfg.reward_scale,
        noise=cfg.noise, random_eps=cfg.random_eps, ou_theta=cfg.ou_theta,
        ou_sigma=cfg.ou_sigma, ou_mu=cfg.ou_mu, device=cfg.actor_device,
    )
    # Actor/env seeds get a per-PROCESS offset: the learner's init seed must
    # be identical on every host (replicated params), but each host's actors
    # must explore decorrelated — without this, all hosts collect the same
    # trajectories and the multi-host fleet adds no data diversity.
    aseed = cfg.seed + 100_003 * jax.process_index()
    actors = []
    for w in range(cfg.n_workers):
        if cfg.her:
            actor = GoalActorWorker(
                f"actor-{w}", config, actor_cfg,
                make_env_fn(cfg, seed=aseed + w)(), service, weights,
                her_ratio=cfg.her_ratio, rng_seed=aseed + w, seed=aseed + w,
                obs_norm=obs_norm,
            )
        else:
            pool = EnvPool(
                [make_env_fn(cfg, seed=aseed + w * cfg.num_envs + i)
                 for i in range(cfg.num_envs)],
                seed=aseed + w,
            )
            actor = ActorWorker(f"actor-{w}", config, actor_cfg, pool, service,
                                weights, seed=aseed + w, obs_dtype=obs_dtype,
                                obs_norm=obs_norm)
        actors.append(actor)
    # Process 0 owns eval (multi-host: other hosts' rollouts would only be
    # discarded — their metrics bus has no sinks).
    evaluator = (
        Evaluator(config, make_env_fn(cfg, seed=cfg.seed + 777), weights,
                  max_steps=cfg.max_steps, goal_conditioned=cfg.her,
                  device=cfg.actor_device, obs_norm=obs_norm)
        if is_main else None
    )
    # Concurrent eval (main.py:395-397: the reference's evaluator is a
    # separate process): greedy rollouts run on a background thread against
    # published weights; the learner never blocks on them.
    async_eval = (AsyncEvaluator(evaluator)
                  if cfg.concurrent_eval and evaluator is not None else None)

    # --- warmup (main.py:200-207); skipped when a restored replay
    # checkpoint already covers it -----------------------------------------
    if len(service) < cfg.warmup:
        warmup_ticks = max(1, cfg.warmup // max(1, cfg.num_envs))
        for actor in actors:
            if cfg.her:
                while actor.env_steps < cfg.warmup // cfg.n_workers:
                    actor.run_episode(cfg.max_steps)
            else:
                actor.run(warmup_ticks // cfg.n_workers)
        service.flush()
    print(f"warmup done: {len(service)} transitions")

    # --- optional network serving for remote actors (actor_main.py) ------
    receiver = weight_server = None
    actor_processes: list = []
    # per-slot respawn bookkeeping (supervisor below): generation varies
    # the child's seed; consecutive failures cap the crash-loop
    actor_proc_gen: list[int] = [0] * max(0, cfg.actor_procs)
    actor_proc_fails: list[int] = [0] * max(0, cfg.actor_procs)
    if cfg.serve or cfg.actor_procs > 0:
        from d4pg_tpu.distributed.transport import TransitionReceiver
        from d4pg_tpu.distributed.weight_plane import WeightPlaneServer

        # K>1: shard-aware receiver — frames forwarded undecoded to the
        # owning ingest shard's worker (raw frames admit on header
        # metadata; npz frames decode at admission, as before). Note the
        # normalizer still folds on the single commit thread in ticket
        # order, so sharding never changes the statistics stream.
        receiver = TransitionReceiver(
            lambda b, aid, count: service.add(b, actor_id=aid,
                                              count_env_steps=count),
            host=cfg.serve_host,
            port=cfg.serve_transitions_port,
            secret=cfg.serve_secret or None,
            num_shards=cfg.ingest_shards,
            on_payload=(service.add_payload if cfg.ingest_shards > 1
                        else None),
            # crash-recovery plane: greet every connecting sender with the
            # live service generation; after a restart-and-restore, frames
            # encoded against the pre-crash service fence at admission
            generation=(lambda: service.generation),
        )
        # Weight plane (docs/architecture.md "Weight plane"): answers
        # BOTH wire protocols on one port — v1 full-snapshot pullers
        # (actor_main.py default) and v2 delta/quantized/fenced pullers
        # (--weight_codec) — with the serialized-frame memo shared.
        weight_server = WeightPlaneServer(weights, host=cfg.serve_host,
                                          port=cfg.serve_weights_port,
                                          secret=cfg.serve_secret or None,
                                          window=cfg.weight_window)
        print(f"serving: transitions :{receiver.port} weights :{weight_server.port}",
              flush=True)
    policy_server = None
    if cfg.serve_policy:
        # Serving plane (docs/architecture.md "Serving plane"): remote
        # actors launched with --policy_port stream obs batches here and
        # get greedy mu back from ONE fused dispatch per batching
        # window; the refresher adopts (generation, version) snapshots
        # from the same store the weight plane broadcasts, under the
        # declared staleness SLA.
        from d4pg_tpu.serving import PolicyInferenceServer

        policy_server = PolicyInferenceServer(
            config, weights, host=cfg.serve_host,
            port=cfg.serve_policy_port,
            secret=cfg.serve_secret or None,
            batch_window_s=cfg.serve_policy_window_s,
            max_batch_rows=cfg.serve_policy_max_rows,
            sla_staleness_s=cfg.serve_policy_sla_s)
        print(f"serving: policy :{policy_server.port}", flush=True)
    if cfg.actor_procs > 0:
        # Real process-level local parallelism (the reference's mp.Process
        # fan-out, main.py:399-405, done over the TCP plane): each process
        # steps its own env pool on the CPU backend and streams in
        # continuously, out of the learner's GIL entirely.
        import multiprocessing as mp

        from d4pg_tpu.actor_main import run_local_actor_process

        ctx = mp.get_context("spawn")
        connect_host = (
            "127.0.0.1" if cfg.serve_host in ("0.0.0.0", "127.0.0.1")
            else cfg.serve_host
        )
        def spawn_actor_proc(i: int, gen: int = 0):
            # stateless by design (replay + weights live with the learner),
            # so the supervisor can respawn with the same config/identity.
            # The seed varies per respawn GENERATION: a respawned child
            # reusing its seed would re-stream duplicate early
            # trajectories into replay (ADVICE r3).
            proc_cfg = dataclasses.replace(
                cfg, seed=aseed + 1000 * (i + 1) + 101 * gen, actor_procs=0,
                serve=False)
            p = ctx.Process(
                target=run_local_actor_process,
                args=(proc_cfg, connect_host, receiver.port,
                      weight_server.port, f"proc-{i}",
                      cfg.serve_secret or None,
                      # both sides are ours: read the generation greeting so
                      # a learner restart fences this child's stale frames
                      True),
                daemon=True,
            )
            p.start()
            return p

        for i in range(cfg.actor_procs):
            actor_processes.append(spawn_actor_proc(i))
        print(f"spawned {len(actor_processes)} actor processes", flush=True)
        if cfg.n_workers == 0:
            # no in-process actors: wait for the fleet to fill the warmup
            if not service.wait_until(cfg.warmup, timeout=300.0):
                raise RuntimeError("actor processes did not reach warmup")

    # --- the HER-paper loop (main.py:299-368), or the decoupled async
    # actor-learner architecture of the D4PG paper (--async_actors 1) ------
    # ``lstep`` mirrors the device step counter on the host (exact: we know
    # how many updates each dispatch performs), so beta/metrics never force
    # a device sync mid-pipeline.
    lstep = int(jax.device_get(state.step))

    # filled by the multi-learner block below (--learners N > 1); empty
    # means the legacy single-learner paths own the weight stream
    replicas: list = []
    mesh_group = None  # mesh-native replica group (collective transport)

    def publish():
        if replicas or mesh_group is not None:
            return  # the merge owns the version stream (one writer)
        p = policy_params(config, state) if mesh is None else jax.device_get(policy_params(config, state))
        weights.publish(p, step=lstep, norm_stats=_norm_snapshot())

    if obs_norm is not None:
        if multi_host:
            # fold every host's warmup rows into the shared statistics
            # before anything trains or republishes (collective)
            obs_norm.sync()
        # warmup just populated the statistics; remote/spawned actors built
        # their FrozenNormalizer from the count-0 pre-warmup publish and
        # won't see a newer weight version until training publishes —
        # re-publish now so the fleet acts on real stats from step one
        publish()

    # Fused K-updates-per-dispatch path. With a mesh this composes with
    # data parallelism: batches are stacked [K, B, ...] with K replicated
    # (the scan axis) and B sharded over ``data``.
    K = max(1, cfg.updates_per_dispatch)
    multi_update = (make_multi_update(config, mesh=mesh, donate=True)
                    if K > 1 and not fused else None)
    chunk_sharding = stacked_sharding(mesh) if mesh is not None else None

    # Fully-fused chunks (learner/fused.py): sample + gather + update +
    # priority write-back inside ONE scanned dispatch against the
    # device-resident ring and trees. The commit -> dispatch -> stage
    # schedule lives in learner/loop.FusedLoop — the SAME class a
    # LearnerReplica drives, so N=1-through-the-aggregator being bitwise
    # the legacy loop is a property of the code structure, not a test
    # that happened to pass once.
    fused_loop = (
        FusedLoop(
            config, buffer, k=K, batch_size=cfg.batch_size,
            prioritized=cfg.prioritized_replay, alpha=cfg.per_alpha,
            beta0=cfg.per_beta0, beta_steps=cfg.per_beta_steps,
            mesh=mesh, service=service, donate=True)
        if fused else None)

    # whole-tree on-device param copy in ONE dispatch (async publish below)
    copy_params = jax.jit(
        lambda p: jax.tree_util.tree_map(jnp.copy, p))

    # Wire-to-grad tracing (docs/architecture.md "Observability plane"):
    # arm the receiver-side span recorder; frames sampled by raw-codec
    # remote actors are followed by their rows' position in host staging.
    # FusedLoop stamps ``grad`` at the dispatch of the first chunk that
    # can sample them and a watcher thread stamps ``done`` when that chunk
    # ends on the device (``wire_to_done``, the headline on this path).
    from d4pg_tpu.obs.trace import RECORDER as trace_recorder

    if cfg.trace_sample > 0:
        trace_recorder.enable(cfg.trace_sample)

    def _publish_async(chunk_state, step):
        """Bounded staleness <= K without stalling the dispatch
        pipeline: an on-device param copy (async dispatch; the next
        chunk's donation would otherwise invalidate the buffers readers
        hold) instead of a blocking D2H pull. Multi-host actors act on
        host arrays (a replicated global array would pin the actor's
        jit to the global mesh), so there the pull is D2H."""
        if multi_host:
            weights.publish(jax.device_get(policy_params(config, chunk_state)),
                            step=step, norm_stats=_norm_snapshot())
        else:
            weights.publish(copy_params(policy_params(config, chunk_state)),
                            step=step, to_host=False,
                            norm_stats=_norm_snapshot())

    def train_steps_fused(n: int):
        """n fused updates through the extracted loop. The only host
        work per chunk is moving staged actor rows onto the device,
        overlapped by FusedLoop's commit/dispatch/stage schedule (≤ 1
        explicit H2D per chunk), so the learner never stalls on a
        transfer. The cycle boundary still flushes everything: training
        each cycle sees all rows the collect phase produced."""
        nonlocal state, lstep

        def on_chunk(chunk_state, k):
            nonlocal lstep
            lstep += k
            if cfg.async_actors:
                _publish_async(chunk_state, lstep)

        state, metrics = fused_loop.run(state, n, on_chunk=on_chunk)
        if metrics is None:
            return None
        return {name: metrics[name][-1]
                for name in ("critic_loss", "actor_loss", "q_mean")}

    # Multi-host PER: all shards must normalize IS weights by the same
    # global max weight — refreshed once per train_steps call (a tiny
    # allgather; p_min drifts slowly within a cycle). None = local
    # normalizer (single-host, exact reference semantics).
    weight_base_cell: dict = {"z": None}

    def _refresh_weight_base():
        if multi_host and cfg.prioritized_replay:
            weight_base_cell["z"] = multihost.global_min_scalar(
                service.weight_base())

    def _sample_chunk():
        """One K-chunk: host tree walks pick [K, B] indices, ONE storage
        gather fetches the rows (device storage: rows stay in HBM)."""
        if cfg.prioritized_replay:
            batches, w, idx, gen = service.sample_chunk(
                K, cfg.batch_size, beta=beta.value(lstep),
                weight_base=weight_base_cell["z"])
            return (batches, w), (idx, gen)
        batches, _, _, _ = service.sample_chunk(K, cfg.batch_size)
        return (batches, None), None

    # Double-buffered host->device staging (SURVEY.md §7 "hard parts"):
    # while the device runs chunk t's scanned update, the host samples and
    # device_puts chunk t+1; PER priority staleness is bounded by (depth+1)K steps.
    # The pipeline itself lives in learner/pipeline.py.
    def _per_write_back(aux, td):
        idx, gen = aux
        for i in range(len(idx)):
            service.update_priorities(idx[i], td[i], generation=gen[i])

    pipeline = (
        ChunkPipeline(
            multi_update, _sample_chunk,
            write_back=_per_write_back if cfg.prioritized_replay else None,
            sharding=chunk_sharding,
            # multi-host: stage chunks by assembling the global [K, B, ...]
            # array from each process's local sample, and pull back only
            # this host's td_error rows for its PER write-back
            put_fn=((lambda payload: multihost.make_global_chunk(payload, mesh))
                    if multi_host else None),
            fetch_td=((lambda m: multihost.local_rows(m["td_error"], axis=1))
                      if multi_host else None),
        )
        if K > 1 and not fused else None
    )

    def _on_chunk(chunk_state):
        """Per-dispatch step accounting + weight publishing. Publishes from
        the CHUNK's output state (the `state` closure variable is rebound
        only after pipeline.run returns — reading it here would ship params
        from before the whole run)."""
        nonlocal lstep
        lstep += K
        if cfg.async_actors:
            p = (policy_params(config, chunk_state) if mesh is None
                 else jax.device_get(policy_params(config, chunk_state)))
            weights.publish(p, step=lstep,  # bounded staleness: lag <= K
                            norm_stats=_norm_snapshot())

    def _stage_single(batch):
        """Place a host-local [B, ...] batch for the update: multi-host
        assembles the global array from every process's local rows (a
        host-local device_put cannot address other hosts' devices); a
        single-host mesh device_puts with the data sharding."""
        if multi_host:
            return multihost.make_global_batch(batch, mesh)
        if mesh is not None:
            return shard_batch(batch, mesh)
        return batch

    def train_single():
        nonlocal state, lstep
        if cfg.prioritized_replay:
            batch, w, idx, gen = service.sample(
                cfg.batch_size, beta=beta.value(lstep),
                weight_base=weight_base_cell["z"])
            w = _stage_single(np.asarray(w, np.float32))
        else:
            batch, w = service.sample(cfg.batch_size), None
        state, metrics = update(state, _stage_single(batch), w)
        lstep += 1
        if cfg.prioritized_replay:
            # each host writes back only ITS rows of the (possibly
            # globally-sharded) td_error — they are the ones its local
            # buffer sampled
            td = (multihost.local_rows(metrics["td_error"], axis=0)
                  if multi_host else np.asarray(metrics["td_error"]))
            service.update_priorities(idx, np.abs(td) + 1e-6, generation=gen)
        return metrics

    def train_steps(n: int):
        """n updates: pipelined K-chunks, then single-dispatch remainder."""
        nonlocal state
        if mesh_group is not None:
            return train_steps_mesh(n)
        if replicas:
            return train_steps_multi(n)
        if fused:
            return train_steps_fused(n)
        _refresh_weight_base()
        metrics = None
        n_chunks, remainder = (n // K, n % K) if K > 1 else (0, n)
        if n_chunks:
            if not cfg.async_actors:
                # Sync mode just collected fresh episodes; drop a chunk
                # sampled before them so every cycle trains on the newest
                # distribution.
                pipeline.invalidate()
            state, metrics = pipeline.run(
                state, n_chunks, on_chunk=_on_chunk,
                final_prefetch=cfg.async_actors,
            )
        for _ in range(remainder):
            metrics = train_single()
        if metrics is None:
            return None
        # last step's scalars for logging (chunk metrics are stacked [K])
        return {
            name: (v if v.ndim == 0 else v[-1])
            for name, v in metrics.items()
            if name in ("critic_loss", "actor_loss", "q_mean")
        }

    # --- multi-learner plane (--learners N > 1) ----------------------------
    # N LearnerReplica threads, each owning a full D4PGState (its own
    # optimizer state + PRNG key), sample the shared ReplayService
    # concurrently; the Aggregator merges their version-stamped updates
    # into the ONE WeightStore stream with IMPACT-style staleness
    # weighting, so actors/relays keep seeing a single monotone
    # (generation, version) sequence (learner/aggregator.py).
    aggregator = None
    replica_failures: dict[int, int] = {}
    pacing_dealer = None  # the sample-on-ingest dealer, if one stands up
    if cfg.learners > 1 or cfg.sample_on_ingest:
        if fused:
            # Unreachable for the device-dealt arm (it forces fused=False
            # above); this guards the FusedLoop learner path proper.
            raise ValueError(
                "--learners > 1 / --sample_on_ingest need the host-sampled "
                "replay path (the FusedLoop learner is single-consumer by "
                "construction — pass --fused_replay off; device-resident "
                "sampling under --sample_on_ingest is --sampler scan, "
                "which owns its fused ring via the dealer)")
        # Merge transport (--agg_transport): 'collective' runs the
        # replicas mesh-native (learner/mesh_replicas.py — full states
        # stacked along the 'replica' mesh axis by partition rule, the
        # merge an on-device collective); 'socket' is the PR-10
        # host-thread plane over 0xD4AB frames and stays the cross-host
        # fallback. 'auto' picks collective exactly when the replicas
        # can share one single-host mesh.
        transport = cfg.agg_transport
        if transport == "auto":
            transport = ("collective"
                         if (mesh is not None and not multi_host
                             and cfg.learners > 1
                             and not cfg.sample_on_ingest)
                         else "socket")
        if transport == "collective":
            if mesh is None or multi_host:
                raise ValueError(
                    "--agg_transport collective needs the replicas on one "
                    "single-host device mesh (--data_parallel/"
                    "--model_parallel); across hosts the socket update "
                    "plane is the fallback")
            if cfg.sample_on_ingest:
                raise ValueError(
                    "--sample_on_ingest deals blocks to host-thread "
                    "replicas — pair it with --agg_transport socket")
            if cfg.learners < 2:
                raise ValueError(
                    "--agg_transport collective needs --learners > 1 "
                    "(with one learner the plain mesh path already "
                    "covers the device layout)")
        elif multi_host or mesh is not None:
            raise ValueError(
                "--agg_transport socket composes with single-host "
                "unmeshed learners only; replicas sharing a device mesh "
                "take --agg_transport collective (the mesh-native merge)")
        if cfg.sample_on_ingest and not cfg.prioritized_replay:
            raise ValueError(
                "--sample_on_ingest is the PER dealer — it needs "
                "--p_replay (dealt blocks carry IS weights)")
        from d4pg_tpu.replay.schedule import SharedBetaSchedule

        n_learners = max(1, cfg.learners)
        # one anneal clock for every sampler in the process: N replicas
        # at the same global step use the same beta (and the dealer
        # stamps it onto the blocks it deals)
        beta_sched = SharedBetaSchedule(beta0=cfg.per_beta0,
                                        beta_steps=cfg.per_beta_steps)
        if transport == "collective":
            from d4pg_tpu.learner.mesh_replicas import MeshReplicaGroup

            rstates = []
            for i in range(n_learners):
                # same replica construction as the socket path below:
                # identical nets, decorrelated keys, per-replica leaf
                # copies (the stacking device_put consumes its inputs)
                rstate = jax.tree_util.tree_map(jnp.copy, state)
                if i:
                    rstate = rstate._replace(
                        key=jax.random.fold_in(rstate.key, i))
                rstates.append(rstate)
            mesh_group = MeshReplicaGroup(
                config, rstates, k=K, batch_size=cfg.batch_size,
                mode=cfg.agg_mode, clip=cfg.agg_clip, store=weights,
                # actors pull acting params only, as with the aggregator
                extract=lambda tree: tree["actor_params"],
                norm_stats=_norm_snapshot, alpha=cfg.per_alpha,
                beta0=cfg.per_beta0, beta_steps=cfg.per_beta_steps)
            print(f"learner plane: {n_learners} mesh-native replicas "
                  f"(collective merge), mode={cfg.agg_mode} "
                  f"clip={cfg.agg_clip}", flush=True)
        else:
            from d4pg_tpu.learner.aggregator import Aggregator
            from d4pg_tpu.learner.replica import LearnerReplica

            dealt_rings: list = []
            if cfg.sample_on_ingest:
                if dealt_arm == "scan":
                    # device-dealt plane: the dealer runs the stratified
                    # descent on device fused behind the commit dispatch
                    # and deals device-resident blocks; rings delete
                    # dropped device blocks eagerly on clear (kill burst)
                    from d4pg_tpu.replay.device_sampler import (
                        DeviceSampleDealer)
                    from d4pg_tpu.replay.staging import DeviceDealtBlockRing

                    dealt_rings = [DeviceDealtBlockRing(4)
                                   for _ in range(n_learners)]
                    dealer = DeviceSampleDealer(
                        cfg.memory_size, dealt_rings, k=K,
                        batch_size=cfg.batch_size, alpha=cfg.per_alpha,
                        beta_schedule=beta_sched,
                        min_size=max(1, cfg.batch_size), seed=cfg.seed)
                else:
                    from d4pg_tpu.replay.sampler import SampleDealer
                    from d4pg_tpu.replay.staging import DealtBlockRing

                    dealt_rings = [DealtBlockRing(4)
                                   for _ in range(n_learners)]
                    dealer = SampleDealer(
                        cfg.memory_size, dealt_rings,
                        n_shards=cfg.ingest_shards, k=K,
                        batch_size=cfg.batch_size, alpha=cfg.per_alpha,
                        beta_schedule=beta_sched,
                        min_size=max(1, cfg.batch_size), seed=cfg.seed)
                service.attach_dealer(dealer)
                pacing_dealer = dealer
            aggregator = Aggregator(
                weights, mode=cfg.agg_mode, clip=cfg.agg_clip,
                # actors pull acting params only; the full 4-subtree merge
                # tree stays between replicas and aggregator
                extract=lambda tree: tree["actor_params"],
                norm_stats=_norm_snapshot)
            for i in range(n_learners):
                # identical network init across replicas, decorrelated
                # sampling/noise keys (replica 0 keeps the original chain).
                # Every replica gets its OWN buffer copy: updates donate
                # their input state, and donated leaves shared between
                # replicas would be deleted under each other
                rstate = jax.tree_util.tree_map(jnp.copy, state)
                if i:
                    rstate = rstate._replace(
                        key=jax.random.fold_in(rstate.key, i))
                replicas.append(LearnerReplica(
                    i, config, aggregator, rstate, k=K,
                    batch_size=cfg.batch_size,
                    prioritized=cfg.prioritized_replay, alpha=cfg.per_alpha,
                    beta0=cfg.per_beta0, beta_steps=cfg.per_beta_steps,
                    service=service,
                    dealt_ring=dealt_rings[i] if dealt_rings else None,
                    beta_schedule=beta_sched))
            print(f"learner plane: {n_learners} replicas, "
                  f"mode={cfg.agg_mode} clip={cfg.agg_clip} "
                  f"sample_on_ingest={cfg.sample_on_ingest}"
                  + (f" sampler={dealt_arm}" if dealt_arm else ""),
                  flush=True)

    # --- Elastic traffic plane (docs/architecture.md "Elastic traffic
    # plane", --autoscale): the obs-driven control loop over whatever
    # capacity knobs this run stood up. Sensing is the obs-registry
    # export the planes already publish; actuation is each owner's
    # bounded live setter (top-level lock acquires only), so the loop
    # adds zero lock edges. Knobs without a wired actuator are still
    # decided and ledgered — the journal shows what the controller
    # WOULD have done on a fuller fleet.
    autoscaler = None
    # active-prefix replica scheduling: train_steps_multi fans each
    # cycle across replicas[:target] only. ``parked`` remembers which
    # replicas sat out a cycle so reactivation goes through respawn()
    # — the idle epoch is fenced and any in-flight submission from
    # before the scale-down bounces at the aggregator instead of
    # landing as a stale surprise.
    replica_target = {"n": max(1, len(replicas)), "parked": set()}
    if cfg.autoscale:
        from d4pg_tpu.elastic.autoscaler import Autoscaler, AutoscalerConfig

        elastic_actuators: dict = {
            "ingest_capacity": service.set_ingest_depth,
        }
        if policy_server is not None:
            elastic_actuators["serving_rows"] = (
                lambda v: policy_server.set_batch_limits(max_rows=v))
            elastic_actuators["serving_window_s"] = (
                lambda v: policy_server.set_batch_limits(window_s=v))
        if pacing_dealer is not None:
            elastic_actuators["dealer_deals"] = pacing_dealer.set_pacing
        if replicas:
            def _set_replica_target(n: int) -> None:
                # autoscaler-thread side records the bounded target
                # only; the train loop adopts it at the next cycle
                # boundary (activation touches the aggregator's epoch
                # table, which belongs to the round-owning thread)
                replica_target["n"] = max(1, min(len(replicas), int(n)))

            elastic_actuators["replicas"] = _set_replica_target
        autoscaler = Autoscaler(
            AutoscalerConfig(
                interval_s=cfg.autoscale_interval_s,
                # anchor the controller's set points at this run's
                # startup knobs so tick 0 is a no-op on a calm fleet
                serving_rows_init=cfg.serve_policy_max_rows,
                serving_rows_min=max(16, cfg.serve_policy_max_rows // 4),
                serving_rows_max=4 * cfg.serve_policy_max_rows,
                serving_window_cold_s=cfg.serve_policy_window_s,
                ingest_capacity_init=256,
                ingest_capacity_min=64,
                ingest_capacity_max=1024,
                replicas_init=max(1, len(replicas)),
                replicas_min=1,
                replicas_max=max(1, len(replicas)),
            ),
            actuators=elastic_actuators).start()
        print(f"elastic: autoscaler up, knobs="
              f"{sorted(elastic_actuators)}", flush=True)

    def train_steps_multi(n: int):
        """Fan the cycle's n grad steps across the replicas: each runs
        ONE basis-adopt -> ceil(n/N) steps -> version-stamped submit
        round on its own thread. Supervision mirrors the actor story: a
        crashed replica is fenced (so its in-flight update bounces at
        the aggregator) and respawned at the next epoch, with the same
        consecutive-failure cap."""
        nonlocal state, lstep
        # adopt the elastic replica target at this cycle boundary:
        # replicas past the prefix sit the cycle out (parked); a parked
        # replica coming back respawns first, fencing its idle epoch
        active = replicas[:replica_target["n"]]
        for r in replicas[len(active):]:
            replica_target["parked"].add(r.replica_id)
        for r in active:
            if r.replica_id in replica_target["parked"]:
                replica_target["parked"].discard(r.replica_id)
                r.respawn()
        per = -(-n // len(active))
        failed: dict[int, str] = {}

        def run_replica(r):
            try:
                r.run_round(per)
            except Exception as e:  # noqa: BLE001 — supervisor owns the verdict
                failed[r.replica_id] = traceback.format_exc()
                contained_crash(f"learner.replica{r.replica_id}", e)

        threads = [
            threading.Thread(target=run_replica, args=(r,), daemon=True)
            for r in active]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for r in active:
            if r.replica_id in failed:
                fails = replica_failures.get(r.replica_id, 0) + 1
                replica_failures[r.replica_id] = fails
                print(f"learner replica {r.replica_id} crashed "
                      f"({fails} consecutive):\n{failed[r.replica_id]}",
                      flush=True)
                if fails >= 5:
                    raise RuntimeError(
                        f"learner replica {r.replica_id} failed {fails} "
                        "cycles in a row; giving up")
                r.respawn()
            else:
                replica_failures[r.replica_id] = 0
        # replica 0's state stands in for `state` downstream (checkpoint,
        # eval lag accounting); the PUBLISHED params are the aggregate
        state = replicas[0].state
        lstep = max([lstep] + [r.steps_done for r in replicas])
        metrics = replicas[0].last_metrics
        if metrics is None:
            return None
        return {name: metrics[name][-1]
                for name in ("critic_loss", "actor_loss", "q_mean")}

    def train_steps_mesh(n: int):
        """The cycle's grad steps on the mesh-native replica group:
        every replica trains ceil(n/N) service-sampled steps against its
        own shard of the replica-stacked state — one [N, K, B, ...]
        dispatch per chunk — then the round closes with the on-device
        collective merge + publish. The socket path's per-round
        device→host pull, 0xD4AB frame and host→device push never
        happen; semantics stay round-synchronous (replica i's
        submission at lag i in async mode)."""
        nonlocal state, lstep
        per = -(-n // mesh_group.n)
        # one beta per round, shared by every replica's sampler — the
        # same anneal clock the thread replicas read
        beta_now = beta_sched.beta_at(beta_sched.current_step())
        metrics = None
        done = 0
        while done < per:
            k = min(K, per - done)
            if cfg.prioritized_replay:
                chunks = [service.sample_chunk(
                    k, cfg.batch_size, beta=beta_now,
                    weight_base=service.weight_base())
                    for _ in range(mesh_group.n)]
                batches = jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *[c[0] for c in chunks])
                w = np.stack(
                    [np.asarray(c[1], np.float32) for c in chunks])
                metrics = mesh_group.step_host_chunks(batches, w)
                # [N, K, B] — replica i's td rows pay back the
                # priorities of the rows IT sampled
                td = np.asarray(metrics["td_error"])
                for i, c in enumerate(chunks):
                    service.update_priorities(
                        c[2], np.abs(td[i]) + 1e-6, generation=c[3])
            else:
                chunks = [service.sample_chunk(k, cfg.batch_size)
                          for _ in range(mesh_group.n)]
                batches = jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *[c[0] for c in chunks])
                metrics = mesh_group.step_host_chunks(batches)
            done += k
        beta_sched.advance(per)
        mesh_group.merge()
        # replica 0's slice stands in for `state` downstream (checkpoint,
        # eval lag accounting); the PUBLISHED params are the merged tree
        state = mesh_group.state_slice(0)
        lstep = max(lstep, mesh_group.steps_done)
        if metrics is None:
            return None
        return {name: np.asarray(metrics[name])[0, -1]
                for name in ("critic_loss", "actor_loss", "q_mean")}

    stop_actors = threading.Event()
    actor_threads: dict[int, threading.Thread] = {}

    def actor_loop(actor):
        try:
            while not stop_actors.is_set():
                if cfg.her:
                    actor.run_episode(cfg.max_steps)
                else:
                    actor.run(50)
        except Exception as e:  # noqa: BLE001 — actor crash must not kill training
            # Log and EXIT the thread; the once-per-cycle supervisor
            # respawns it, which also rate-limits a permanently failing
            # actor to one attempt per cycle.
            print(f"actor {actor.actor_id} crashed:\n{traceback.format_exc()}",
                  flush=True)
            contained_crash(f"actor.{actor.actor_id}", e)

    def start_actor_thread(i: int):
        t = threading.Thread(target=actor_loop, args=(actors[i],), daemon=True)
        t.start()
        actor_threads[i] = t

    def supervise_actors():
        """Failure recovery (SURVEY.md §5 — the reference has none): actors
        are stateless-restartable, so a dead thread is simply respawned."""
        for i, t in list(actor_threads.items()):
            if not t.is_alive() and not stop_actors.is_set():
                print(f"supervisor: restarting actor thread {i}", flush=True)
                start_actor_thread(i)

    if cfg.async_actors:
        for i in range(len(actors)):
            start_actor_thread(i)

    timer = StepTimer()
    last_metrics: dict = {}
    # XLA compilations the learner thread made inside each cycle's train
    # bracket: everything compiles in cycle 1; a later nonzero entry is a
    # steady-state recompile stalling the learner
    compiles_by_cycle: list[int] = []
    n_saves = 0
    if multi_host:
        # align the first sharded update across processes (warmup and
        # io/eval setup take different time per role)
        multihost.barrier("train_start")
    for epoch in range(cfg.n_epochs):
        for cycle in range(cfg.n_cycles):
            cycle_t0 = time.monotonic()
            # collect (sync mode; async actors stream in the background)
            if not cfg.async_actors:
                for actor in actors:
                    if cfg.her:
                        for _ in range(cfg.episodes_per_cycle):
                            actor.run_episode(cfg.max_steps)
                    else:
                        ticks = cfg.episodes_per_cycle * cfg.max_steps // max(
                            1, cfg.num_envs)
                        actor.run(ticks)
                service.flush()
            if multi_host and obs_norm is not None:
                # collective: merge every host's normalizer delta so all
                # hosts standardize with identical statistics this cycle
                obs_norm.sync()
            # train. --profile_dir traces ONE cycle: the second where
            # there is one (the first is the compile), with the program's
            # own spans (obs/trace.span) beside the device's op line.
            timer.start()
            nth_cycle = epoch * cfg.n_cycles + cycle
            traced_cycle = min(1, cfg.n_epochs * cfg.n_cycles - 1)
            with xla_trace(cfg.profile_dir if nth_cycle == traced_cycle
                           else None), \
                    RecompileSentinel(same_thread=True) as compiles:
                metrics = train_steps(cfg.train_steps_per_cycle)
                # the fused path returns once the cycle is ENQUEUED: wait
                # for its last metrics (fetched just below anyway), so the
                # timer reads a completion rate and the trace holds the
                # device's work
                jax.block_until_ready(metrics)
            rate = timer.stop(cfg.train_steps_per_cycle)
            compiles_by_cycle.append(compiles.compilations)
            if nth_cycle == 0:
                # the first chunks are done: where this process's start-up
                # went, once (obs/startup_log.py; README, "Start-up")
                print(startup_log.LOG.table(), flush=True)
            # weight staleness actors saw this cycle, measured before the
            # cycle-end publish (<= K in async mode, one cycle in sync mode)
            weight_lag = lstep - weights.step
            publish()
            # eval + log (main.py:309-353). Concurrent mode: request a fresh
            # eval against the just-published weights and log the most
            # recent COMPLETED one; the learner thread never waits.
            eval_seed = cfg.seed + epoch * 1000 + cycle
            if async_eval is not None:
                async_eval.request(cfg.eval_trials, seed=eval_seed)
                eval_metrics = async_eval.latest()
            elif evaluator is not None:
                eval_metrics = evaluator.evaluate(cfg.eval_trials,
                                                  seed=eval_seed)
            else:
                eval_metrics = None
            last_metrics = {
                "critic_loss": float(jax.device_get(metrics["critic_loss"])),
                "actor_loss": float(jax.device_get(metrics["actor_loss"])),
                "env_steps": service.env_steps,
                "weight_lag_steps": weight_lag,
                "learner_compiles": compiles.compilations,
            }
            if eval_metrics is not None:
                last_metrics.update({
                    "avg_test_reward": eval_metrics["avg_test_reward"],
                    "ewma_test_reward": eval_metrics["ewma_test_reward"],
                    "success_rate": eval_metrics["success_rate"],
                    "eval_lag_steps": lstep - eval_metrics["learner_step"],
                })
            if rate is not None:
                last_metrics["grad_steps_per_sec"] = round(rate, 2)
            if cfg.trace_sample > 0:
                # wire-to-grad headline onto the metrics bus: the p95 of
                # the end-to-end span over the recent trace window
                lat = trace_recorder.latency_block()
                for headline in ("wire_to_grad", "wire_to_done"):
                    if lat[headline]["n"]:
                        last_metrics[headline + "_p95_ms"] = \
                            lat[headline]["p95"]
            last_metrics["cycle_time_s"] = round(time.monotonic() - cycle_t0, 4)
            # Failure detection/recovery (SURVEY.md §5): stale heartbeats
            # reach the metrics bus (not just stdout); dead spawned actor
            # PROCESSES are respawned like dead threads — they are
            # stateless, replay and weights live with the learner. Remote
            # actors (other machines) can only be observed, not respawned.
            # Heartbeat liveness is only meaningful for STREAMING actors
            # (async threads, spawned procs, remote fleets) — synchronous
            # in-process actors ingest exactly once per cycle, so any slow
            # cycle would trip the timeout spuriously.
            track_liveness = (cfg.async_actors or cfg.actor_procs > 0
                              or cfg.serve)
            dead = service.dead_actors() if track_liveness else []
            last_metrics["dead_actors"] = len(dead)
            if dead:
                print(f"WARNING: actors missing heartbeats: {dead}", flush=True)
            for i, p in enumerate(actor_processes):
                if p is None:  # slot retired after repeated crash-looping
                    continue
                if p.is_alive():
                    actor_proc_fails[i] = 0
                    continue
                # once-per-cycle cadence already rate-limits respawns; the
                # consecutive-failure cap stops a child that cannot start
                # at all (bad GL/env config) from crash-looping forever
                # (ADVICE r3)
                actor_proc_fails[i] += 1
                if actor_proc_fails[i] > 5:
                    print(f"supervisor: actor process {i} died "
                          f"{actor_proc_fails[i]} consecutive cycles "
                          f"(exitcode {p.exitcode}); giving up on this "
                          "slot", flush=True)
                    actor_processes[i] = None
                    continue
                actor_proc_gen[i] += 1
                print(f"supervisor: restarting actor process {i} "
                      f"(exitcode {p.exitcode}, respawn "
                      f"#{actor_proc_gen[i]})", flush=True)
                actor_processes[i] = spawn_actor_proc(i, actor_proc_gen[i])
            if cfg.async_actors:
                supervise_actors()
            bus.log(lstep, last_metrics)
            if (cycle + 1) % cfg.checkpoint_every == 0:
                n_saves += 1
                replay_due = (
                    cfg.checkpoint_replay
                    and n_saves % max(1, cfg.checkpoint_replay_every) == 0)
                if ckpt is not None:
                    extra_payload = {"env_steps": service.env_steps}
                    if obs_norm is not None:
                        extra_payload["obs_norm"] = obs_norm.state_dict()
                    ckpt.save(
                        state if mesh is None else jax.device_get(state),
                        extra=extra_payload,
                    )
                if replay_due:
                    if ckpt is not None:
                        # durability order: the state checkpoint commits
                        # BEFORE the sidecar rename (Orbax saves async) —
                        # a crash in this window must never leave a
                        # sidecar AHEAD of the latest durable state, which
                        # restore would refuse, emptying the buffer (the
                        # exact failure the sidecar exists to prevent)
                        ckpt.wait()
                    # every host's SERVICE snapshot goes to its step-stamped
                    # sidecar (process 0 included) at a coarser cadence than
                    # the state checkpoint — the ring snapshot holds the
                    # buffer lock and (device storage) pays a full D2H copy.
                    # A service snapshot (vs the old buffer-only dict) also
                    # carries the admission-ticket floor + generation, so a
                    # crash-restart fences pre-crash frames and resumes
                    # merge-ordered. Restore tolerates the resulting
                    # staleness; an Orbax extra payload would instead vanish
                    # whenever the retention window outran the replay
                    # cadence.
                    _save_host_replay(run_dir, jax.process_index(), lstep,
                                      service.snapshot(quiesce_timeout=2.0))
    stop_actors.set()
    for t in actor_threads.values():
        t.join(timeout=10.0)
    if async_eval is not None:
        # Drain the last requested eval so the returned metrics reflect the
        # final published weights, then log it.
        final_eval = async_eval.wait()
        async_eval.close()
        if final_eval is not None:
            last_metrics.update({
                "avg_test_reward": final_eval["avg_test_reward"],
                "ewma_test_reward": final_eval["ewma_test_reward"],
                "success_rate": final_eval["success_rate"],
                "eval_lag_steps": lstep - final_eval["learner_step"],
            })
            bus.log(lstep, last_metrics)
    if ckpt is not None:
        ckpt.wait()
    bus.close()
    for p in actor_processes:
        if p is not None:
            p.terminate()
    for p in actor_processes:
        if p is not None:
            p.join(timeout=5.0)
    if autoscaler is not None:
        # first: a tick firing mid-teardown would actuate knobs on
        # planes that are already half-closed below
        autoscaler.close()
    for r in replicas:
        r.close()
    if aggregator is not None:
        aggregator.close()
    if mesh_group is not None:
        mesh_group.close()
    if fused_loop is not None:
        fused_loop.close()
    if receiver is not None:
        receiver.close()
    if weight_server is not None:
        weight_server.close()
    if policy_server is not None:
        policy_server.close()
    service.close()
    for actor in actors:
        if cfg.her:
            actor.env.close()
        else:
            actor.pool.close()
    if multi_host:
        # align exits: a process leaving while a peer still drains eval/
        # checkpoints trips the jax.distributed shutdown barrier
        multihost.barrier("train_end")
    # after the last bus.log: these are for the caller, not the sinks
    last_metrics["learner_step"] = lstep
    last_metrics["plan"] = plan
    last_metrics["compiles_by_cycle"] = compiles_by_cycle
    return last_metrics


def main(argv=None) -> dict:
    cfg = parse_args(argv)
    # the one backend rule (d4pg_tpu/startup.py): the chip unless CPU was
    # asked for, and never a fallback from one to the other
    from d4pg_tpu import startup

    startup.configure(cfg.platform)
    if cfg.coordinator:
        # Join the multi-host runtime BEFORE any backend init; after this,
        # jax.devices() spans every process and --data_parallel can cover
        # the global device count (parallel/multihost.py). Each host runs
        # this same command with its own --process_id.
        from d4pg_tpu.parallel import multihost

        multihost.initialize(cfg.coordinator, cfg.num_processes,
                             cfg.process_id)
        # create the collective context NOW, while processes are in
        # lockstep (per-role io/eval setup later skews them past the
        # context-init timeout)
        multihost.barrier("startup")
        print(f"joined multi-host runtime: process {cfg.process_id}/"
              f"{cfg.num_processes}, {len(jax.devices())} global devices",
              flush=True)
    startup.describe()
    result = train(cfg)
    print("final:", result)
    return result


if __name__ == "__main__":
    main()
