"""Typed experiment configuration + CLI front-end.

Parity: the reference's argparse surface (``main.py:31-56``) and its
config-mutating hooks (``main.py:84-99, 379-380``), as a frozen dataclass
with per-env presets (SURVEY.md §5 config-system mandate). Every reference
flag maps to a field; flags the reference exposes but never wires live
(``--ou_theta/--ou_sigma/--ou_mu``, SURVEY.md C6) are wired for real via
``noise='ou'``. Run-dir naming encodes the config like the reference's
``runs/exp_<env>__PER?_HER?_<n>N_<k>Workers`` (``main.py:59-66``).
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from d4pg_tpu.envs.presets import get_preset, has_preset
from d4pg_tpu.learner.state import D4PGConfig


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    # env
    env: str = "Pendulum-v1"  # --env
    # episode horizon; None = from the env preset when one is curated, else
    # 200 (the reference's --max_steps default). An explicit value always
    # wins over the preset.
    max_steps: int | None = None  # --max_steps
    num_envs: int = 4  # vectorized pool width (reference: 1)
    her: bool = False  # --her
    her_ratio: float = 0.8  # main.py:165
    # pixel-obs rendering size (dm_control adapter) and conv-encoder width;
    # the 84px/32ch DrQ defaults cost ~40 GFLOP per grad step — smaller
    # settings make pixel training tractable on modest hosts
    pixel_size: int = 84
    encoder_width: int = 32
    # frames stacked along the channel axis for pixel envs (FrameStack
    # wrapper). 1 = raw single frames (a POMDP for dynamic tasks —
    # velocities are invisible); 3 is the DrQ/D4PG-pixels convention and
    # the right setting for dm_control pixel control.
    frame_stack: int = 1
    # DrQ random-shift augmentation inside the jit'd update (pixel envs
    # only): 'none' or 'shift'; the shift radius should roughly scale with
    # the frame size (DrQ's 4px is calibrated to 84px frames)
    augment: str = "none"
    augment_pad: int = 4
    # tie the actor's conv encoder to the critic's, trained by the critic
    # loss only (SAC-AE/DrQ; pixels only — see learner/state.py)
    share_encoder: bool = False
    # a sequence torso shared by actor and critic (models/torso.py): the
    # path of a JSON file holding the torso block (``name``, ``tokens``,
    # the widths ...), either as the whole file or under ``model.torso``
    # as a benchmark configuration file has it. The env's observations
    # become the history the torso reads (envs/wrappers.History).
    torso: str = ""
    reward_scale: float = 1.0
    # replay
    memory_size: int = 1_000_000  # --rmsize
    batch_size: int = 64  # --bsize
    warmup: int = 5000  # --warmup (main.py:200-207)
    prioritized_replay: bool = True  # --p_replay
    per_alpha: float = 0.6  # ddpg.py:81
    per_beta0: float = 0.4  # ddpg.py:84
    per_beta_steps: int = 100_000  # ddpg.py:85
    # n-step return horizon; None = from a curated env preset, else 3
    n_steps: int | None = None  # --n_steps
    # 'device': transition ring in accelerator HBM (host keeps PER trees,
    # picks indices; per-dispatch H2D is O(indices) not O(batch bytes));
    # 'auto' selects device on an accelerator single-device learner.
    replay_storage: str = "auto"
    # Fully-fused replay+learn path (learner/fused.py): PER trees join the
    # ring in HBM and sample/gather/update/priority-write-back all run
    # inside the scanned dispatch — zero per-chunk host round trips, zero
    # priority staleness. 'auto' = on whenever storage resolves to device
    # and the learner is single-device; 'off' keeps host trees.
    fused_replay: str = "auto"
    # K learner updates fused into one device dispatch via lax.scan.
    # A dispatch costs far more than one step's compute at these model
    # sizes, so the per-dispatch cost is amortised over K steps (the rate
    # at each K is not measured on the current machine — see PERF.md).
    # 40 = one dispatch per HER-paper cycle (main.py:303-307's 40 train
    # steps). On the fused path priorities
    # still update per-step INSIDE the scan (zero staleness); the host
    # pipeline's write-back lags <= (depth+1)K, default 3K. Async weight staleness <= K.
    # Composes with data_parallel (batches sharded P(None, 'data')).
    # 1 = exact reference dispatch semantics (write-back every step).
    updates_per_dispatch: int = 40
    # Multi-learner plane (learner/replica.py + learner/aggregator.py):
    # N replicas each own a full D4PGState (their OWN optimizer state and
    # PRNG key) and sample the shared ReplayService concurrently; an
    # aggregator merges their version-stamped updates into the ONE
    # WeightStore stream with IMPACT-style staleness weighting (arXiv
    # 1912.00167). 1 = the legacy fused single-learner loop (same code:
    # both paths drive learner/loop.FusedLoop). N > 1 requires the
    # host-sampled replay path (fused device replay is single-consumer).
    learners: int = 1  # --learners
    # Sample-on-ingest (docs/architecture.md "Sample-on-ingest"): PER
    # sampling runs on the receive path — the commit thread deals
    # ready-to-train blocks into per-replica rings inside its own
    # buffer-lock window, and replicas feed TD priorities back through a
    # generation-fenced write-back queue. Requires the host replay path
    # (--fused_replay off) with prioritized replay.
    sample_on_ingest: bool = False
    # Sample-path arm for --sample_on_ingest
    # (replay/device_sampler.resolve_sampler): 'auto' is 'scan' on a TPU
    # and 'host' elsewhere; 'scan' = device gather descent fused behind
    # the commit dispatch; 'host' = the PR-12 host SampleDealer (host tree
    # math, pinned bitwise-equal to the device path under the
    # seeded-stream oracle). 'scan' requires --ingest_shards 1 and no mesh
    # (the commit thread owns every device handle); 'host' requires the
    # host replay path.
    sampler: str = "auto"
    # 'async': clipped importance-weighted staleness correction, no
    # barrier; 'sync': plain N-way averaging barrier per round
    agg_mode: str = "async"
    # staleness-weight clip: a stale update's weight is
    # max(1/(1+lag), 1/agg_clip) — the floor keeps a lagging replica's
    # vote bounded away from zero (>= 1; higher tolerates more staleness)
    agg_clip: float = 8.0
    # How replica updates reach the merge (learner/mesh_replicas.py):
    # 'collective' = mesh-native — replica states sharded along the
    # 'replica' mesh axis, the merge an on-device collective (requires
    # the replicas to share one single-host mesh); 'socket' = the PR-10
    # host-thread aggregator over 0xD4AB frames (works anywhere; the
    # cross-host fallback); 'auto' = collective when a mesh is present
    # and single-host, socket otherwise.
    agg_transport: str = "auto"
    # algorithm
    gamma: float = 0.99  # --gamma
    tau: float = 0.001  # --tau
    # HER-recipe action-L2 penalty on the actor loss (0 = reference objective)
    action_l2: float = 0.0
    lr_actor: float = 1e-4
    lr_critic: float = 1e-3
    adam_b1: float = 0.9
    adam_b2: float = 0.999  # reference (0.9, 0.9) available via flags
    v_min: float | None = None  # --v_min (None: from preset)
    v_max: float | None = None  # --v_max
    n_atoms: int = 51  # --n_atoms
    critic_family: str = "categorical"
    hidden: tuple = (256, 256, 256)
    compute_dtype: str = "float32"  # 'bfloat16' for MXU-native matmuls
    # exploration
    noise: str = "gaussian"  # 'gaussian' | 'ou'
    # per-tick probability of a uniform random action (HER-recipe
    # epsilon-greedy; 0 = reference's additive-noise-only exploration)
    random_eps: float = 0.0
    # Running observation standardization (envs/normalizer.py): actors
    # store normalized rows, eval applies the same stats; off = reference
    # behavior (no normalization anywhere). Vector obs only (the pixel
    # encoder normalizes by /255). HER-recipe component for Fetch/Hand.
    normalize_obs: bool = False
    normalize_clip: float = 5.0  # +-clip after standardization (HER paper)
    epsilon_0: float = 0.3  # random_process.py:11
    min_epsilon: float = 0.01
    epsilon_horizon: int = 5000
    ou_theta: float = 0.25  # --ou_theta (main.py:36, dead in reference)
    ou_sigma: float = 0.05  # --ou_sigma
    ou_mu: float = 0.0  # --ou_mu
    # Backend for actor/evaluator inference: 'cpu' pins the per-tick policy
    # forward to host CPU (the accelerator stays the learner's; a per-step
    # device round trip costs more than the MLP forward), 'default' follows
    # the default backend (see ActorConfig.device).
    actor_device: str = "cpu"
    # loop shape (main.py:299-312)
    n_epochs: int = 20  # --n_eps
    n_cycles: int = 50
    episodes_per_cycle: int = 16
    train_steps_per_cycle: int = 40
    eval_trials: int = 10
    # Evaluate on a background thread (the reference's separate evaluator
    # process, main.py:395-397); 0 = inline on the learner thread.
    concurrent_eval: bool = True
    # distributed
    n_workers: int = 1  # --n_workers (in-process actor threads)
    # Multi-host runtime (jax.distributed): every host starts the same
    # train command with its own --process_id; process 0's host:port is
    # the coordinator. Empty coordinator = single-process (default).
    coordinator: str = ""
    # Backend for the learner (d4pg_tpu/startup.py, the one rule every
    # entry point shares): 'tpu' requires the chip and fails loudly when
    # it cannot initialise; 'cpu' is the explicit host-backend request.
    # A JAX_PLATFORMS set by the caller is honoured over the default.
    # Read by the entry points only (train.main); programmatic train()
    # callers own their process's backend.
    platform: str = "tpu"
    num_processes: int = 1
    process_id: int = 0
    # Spawned local actor PROCESSES connecting through the TCP plane
    # (implies --serve): real parallelism for host-bound env stepping,
    # unlike in-process actor threads which share the learner's GIL.
    actor_procs: int = 0
    data_parallel: int = 1  # learner mesh data axis (1 = single device)
    async_actors: bool = False  # decoupled D4PG-paper actor/learner loop
    serve: bool = False  # accept remote actors (actor_main.py) over TCP
    serve_host: str = "127.0.0.1"  # bind address; set the DCN iface for fleets
    serve_secret: str = ""  # shared secret gating remote peers ('' = open)
    serve_transitions_port: int = 0  # 0 = ephemeral
    serve_weights_port: int = 0
    # Serving plane (docs/architecture.md "Serving plane"): stand up the
    # continuous-batching PolicyInferenceServer next to the transition/
    # weight servers so remote actors launched with ``--policy_port``
    # query greedy actions instead of acting locally. Window/row-budget
    # knobs bound the batcher's coalescing; the staleness SLA is the
    # declared freshness bound (breaches are counted, not fatal).
    serve_policy: bool = False
    serve_policy_port: int = 0  # 0 = ephemeral
    serve_policy_window_s: float = 0.002
    serve_policy_max_rows: int = 256
    serve_policy_sla_s: float = 1.0
    # Elastic traffic plane (docs/architecture.md "Elastic traffic
    # plane"): run the obs-driven autoscaler thread next to the serving/
    # ingest planes — it polls the obs-registry providers and live-
    # adjusts the serving batch limits, ingest shard depth, dealer
    # pacing, and active learner-replica count through their bounded
    # setters, journaling every decision in a replayable ScalingLedger.
    # Off = every capacity knob stays at its startup value (the
    # pre-elastic behaviour, bit for bit).
    autoscale: bool = False
    autoscale_interval_s: float = 0.25
    # Weight-broadcast version window (docs/architecture.md "Weight
    # plane"): the server keeps this many recent versions so pullers
    # inside the window receive per-tensor deltas instead of full
    # snapshots; pullers outside it (or across a learner restart's
    # generation bump) fall back to a full frame.
    weight_window: int = 8
    # Receiver-side ingest shards (docs/architecture.md "Sharded
    # receiver"): K SO_REUSEPORT listeners + K decode/stage workers + one
    # ordered merge-commit thread. 1 = the legacy single-drain plane.
    ingest_shards: int = 1
    # Wire-to-grad tracing (docs/architecture.md "Observability plane"):
    # arms the learner-side trace recorder and stamps grad-consumption
    # spans after each fused dispatch; remote actors sample frames at
    # this rate when launched with ``--codec raw --trace_sample <f>``.
    # 0 = fully inert (no recorder, no per-chunk hook).
    trace_sample: float = 0.0
    profile_dir: str = ""  # capture an XLA trace of the first cycle
    # io
    log_dir: str = "runs"  # --log_dir
    seed: int = 0
    checkpoint_every: int = 1  # cycles between checkpoints (main.py:367)
    # Also checkpoint the replay buffer (contents + PER priorities) for
    # EXACT elastic recovery — without it a resumed learner retrains from
    # an empty buffer through a fresh warmup. Off by default: the payload
    # is the whole ring (GBs at 1M Humanoid transitions).
    checkpoint_replay: bool = False
    # Ring payloads ride only every Nth checkpoint: the snapshot holds the
    # buffer lock (stalling actor ingest) and for a device-resident ring
    # pays a full D2H copy, so per-cycle would be pathological. A resume
    # whose latest checkpoint lacks the payload just re-runs warmup.
    checkpoint_replay_every: int = 10
    resume: bool = False
    # One-flag parity mode: the reference's own hyperparameters — v_min/
    # v_max from its per-env hook (main.py:84-99), Adam betas (0.9, 0.9)
    # (shared_adam.py:4), lr 1e-3 for both nets (main.py:384-385,
    # n_workers=1), no reward scaling, and single-dispatch updates (exact
    # per-step priority write-back like ddpg.py:252-255).
    strict_reference: bool = False

    def run_name(self) -> str:
        """Config-encoded run dir (parity: ``main.py:59-64``). Resolves
        first so a preset-defaulted n_steps (None until resolve) encodes
        identically on resolved and unresolved configs."""
        cfg = self.resolve()
        return (
            f"exp_{cfg.env}_"
            f"{'_PER' if cfg.prioritized_replay else ''}"
            f"{'_HER' if cfg.her else ''}"
            f"_{cfg.n_steps}N_{cfg.n_workers}Workers"
        )

    def resolve(self) -> "ExperimentConfig":
        """Fill v_min/v_max (+ reward scale / horizon) from the env preset
        when unset (the ``configure_env_params`` hook, ``main.py:84-99``).
        ``strict_reference`` switches to the reference's own preset values
        and training hyperparameters wholesale."""
        preset = get_preset(self.env, strict=self.strict_reference)
        curated = has_preset(self.env, strict=self.strict_reference)
        updates: dict = {}
        if self.v_min is None:
            updates["v_min"] = preset.v_min
        if self.v_max is None:
            updates["v_max"] = preset.v_max
        if self.reward_scale == 1.0 and preset.reward_scale != 1.0:
            updates["reward_scale"] = preset.reward_scale
        # horizon / n-step: unset (None) -> curated preset value, else the
        # reference defaults (200 / 3); explicit values always win, and the
        # fallback preset's own field defaults never masquerade as curation
        if self.max_steps is None:
            updates["max_steps"] = preset.max_steps if curated else 200
        if self.n_steps is None:
            updates["n_steps"] = preset.n_step if curated else 3
        if self.strict_reference:
            updates.update(
                reward_scale=1.0,
                lr_actor=1e-3,  # main.py:384-385 at n_workers=1
                lr_critic=1e-3,
                adam_b1=0.9,  # shared_adam.py:4
                adam_b2=0.9,
                updates_per_dispatch=1,  # per-step write-back, ddpg.py:252-255
            )
        return dataclasses.replace(self, **updates) if updates else self

    def torso_block(self) -> dict | None:
        """The torso block ``--torso`` names, or None."""
        if not self.torso:
            return None
        import json

        with open(self.torso) as f:
            block = json.load(f)
        return block.get("model", block).get("torso", block)

    def learner_config(self, obs_dim: int | tuple, act_dim: int) -> D4PGConfig:
        """``obs_dim`` is an int (vector obs) or an [H, W, C] tuple, which
        selects the conv-encoder pixel path (BASELINE.md config #4)."""
        resolved = self.resolve()
        pixels = not np.isscalar(obs_dim)
        return D4PGConfig(
            obs_dim=int(np.prod(obs_dim)) if pixels else obs_dim,
            pixels=pixels,
            obs_shape=tuple(obs_dim) if pixels else (),
            act_dim=act_dim,
            v_min=float(resolved.v_min),
            v_max=float(resolved.v_max),
            n_atoms=self.n_atoms,
            hidden=tuple(self.hidden),
            critic_family=self.critic_family,
            augment=self.augment,
            augment_pad=self.augment_pad,
            share_encoder=self.share_encoder,
            torso=self.torso_block(),
            encoder_channels=(self.encoder_width,) * 4,
            lr_actor=self.lr_actor,
            lr_critic=self.lr_critic,
            adam_b1=self.adam_b1,
            adam_b2=self.adam_b2,
            compute_dtype=self.compute_dtype,
            tau=self.tau,
            gamma=self.gamma,
            action_l2=self.action_l2,
        )


def _add_bool_flag(parser: argparse.ArgumentParser, name: str, default: bool, help_: str):
    """0/1 int flags like the reference's --p_replay/--her/--multithread
    (``main.py:44`` quirk: --debug as type=bool parses any string truthy —
    not reproduced)."""
    parser.add_argument(f"--{name}", type=int, choices=(0, 1),
                        default=int(default), help=help_)


def build_parser() -> argparse.ArgumentParser:
    d = ExperimentConfig()
    p = argparse.ArgumentParser(
        prog="d4pg_tpu.train",
        description="TPU-native D4PG (capability parity with ajgupta93/d4pg-pytorch)",
    )
    p.add_argument("--env", default=d.env)
    p.add_argument("--max_steps", type=int, default=d.max_steps)
    p.add_argument("--num_envs", type=int, default=d.num_envs)
    _add_bool_flag(p, "her", d.her, "hindsight experience replay")
    p.add_argument("--her_ratio", type=float, default=d.her_ratio)
    p.add_argument("--pixel_size", type=int, default=d.pixel_size,
                   help="dm_control pixel render height/width")
    p.add_argument("--encoder_width", type=int, default=d.encoder_width,
                   help="conv-encoder channel width (4 layers)")
    p.add_argument("--frame_stack", type=int, default=d.frame_stack,
                   help="frames stacked channel-wise for pixel envs "
                        "(1 = raw frames; 3 = DrQ/D4PG-pixels convention "
                        "— single frames hide velocities)")
    p.add_argument("--augment", choices=("none", "shift"), default=d.augment,
                   help="batch image augmentation in the update (pixel "
                        "envs): 'shift' = DrQ random shift")
    p.add_argument("--augment_pad", type=int, default=d.augment_pad,
                   help="shift radius in pixels (DrQ uses 4 at 84px; "
                        "scale with --pixel_size)")
    _add_bool_flag(p, "share_encoder", d.share_encoder,
                   "critic-trained shared conv encoder (SAC-AE/DrQ; "
                   "pixel envs)")
    p.add_argument("--torso", default=d.torso,
                   help="JSON file naming a sequence torso shared by actor "
                        "and critic (models/torso.py): the torso block "
                        "itself, or a benchmark configuration file with it "
                        "under model.torso; observations become a history "
                        "of torso.tokens values")
    p.add_argument("--rmsize", type=int, default=d.memory_size, dest="memory_size")
    p.add_argument("--bsize", type=int, default=d.batch_size, dest="batch_size")
    p.add_argument("--warmup", type=int, default=d.warmup)
    _add_bool_flag(p, "p_replay", d.prioritized_replay, "prioritized replay")
    p.add_argument("--per_alpha", type=float, default=d.per_alpha)
    p.add_argument("--per_beta0", type=float, default=d.per_beta0)
    p.add_argument("--per_beta_steps", type=int, default=d.per_beta_steps)
    p.add_argument("--n_steps", type=int, default=d.n_steps)
    p.add_argument("--replay_storage", choices=("auto", "host", "device"),
                   default=d.replay_storage)
    p.add_argument("--fused_replay", choices=("auto", "on", "off"),
                   default=d.fused_replay)
    p.add_argument("--updates_per_dispatch", type=int,
                   default=d.updates_per_dispatch)
    p.add_argument("--gamma", type=float, default=d.gamma)
    p.add_argument("--tau", type=float, default=d.tau)
    p.add_argument("--action_l2", type=float, default=d.action_l2)
    p.add_argument("--lr_actor", type=float, default=d.lr_actor)
    p.add_argument("--lr_critic", type=float, default=d.lr_critic)
    p.add_argument("--adam_b1", type=float, default=d.adam_b1)
    p.add_argument("--adam_b2", type=float, default=d.adam_b2)
    p.add_argument("--v_min", type=float, default=None)
    p.add_argument("--v_max", type=float, default=None)
    p.add_argument("--n_atoms", type=int, default=d.n_atoms)
    p.add_argument("--critic_family", choices=("categorical", "mog"),
                   default=d.critic_family)
    p.add_argument("--compute_dtype", choices=("float32", "bfloat16"),
                   default=d.compute_dtype)
    p.add_argument("--noise", choices=("gaussian", "ou"), default=d.noise)
    p.add_argument("--epsilon_0", type=float, default=d.epsilon_0)
    p.add_argument("--random_eps", type=float, default=d.random_eps)
    _add_bool_flag(p, "normalize_obs", d.normalize_obs,
                   "running observation standardization")
    p.add_argument("--normalize_clip", type=float, default=d.normalize_clip)
    p.add_argument("--ou_theta", type=float, default=d.ou_theta)
    p.add_argument("--ou_sigma", type=float, default=d.ou_sigma)
    p.add_argument("--ou_mu", type=float, default=d.ou_mu)
    p.add_argument("--actor_device", choices=("cpu", "default"),
                   default=d.actor_device)
    p.add_argument("--n_eps", type=int, default=d.n_epochs, dest="n_epochs")
    p.add_argument("--n_cycles", type=int, default=d.n_cycles)
    p.add_argument("--episodes_per_cycle", type=int, default=d.episodes_per_cycle)
    p.add_argument("--train_steps_per_cycle", type=int,
                   default=d.train_steps_per_cycle)
    p.add_argument("--eval_trials", type=int, default=d.eval_trials)
    _add_bool_flag(p, "concurrent_eval", d.concurrent_eval,
                   "evaluate on a background thread")
    p.add_argument("--n_workers", type=int, default=d.n_workers)
    p.add_argument("--actor_procs", type=int, default=d.actor_procs)
    p.add_argument("--coordinator", default=d.coordinator)
    p.add_argument("--platform", choices=("tpu", "cpu"),
                   default=d.platform,
                   help="'tpu' (default) requires the chip — no CPU "
                        "fallback; 'cpu' runs on the host backend. A "
                        "caller-set JAX_PLATFORMS wins over the default")
    p.add_argument("--num_processes", type=int, default=d.num_processes)
    p.add_argument("--process_id", type=int, default=d.process_id)
    p.add_argument("--data_parallel", type=int, default=d.data_parallel)
    _add_bool_flag(p, "async_actors", d.async_actors,
                   "decoupled actor/learner loop")
    _add_bool_flag(p, "serve", d.serve, "accept remote actors over TCP")
    p.add_argument("--serve_host", default=d.serve_host)
    p.add_argument("--serve_secret", default=d.serve_secret)
    p.add_argument("--serve_transitions_port", type=int,
                   default=d.serve_transitions_port)
    p.add_argument("--serve_weights_port", type=int, default=d.serve_weights_port)
    _add_bool_flag(p, "serve_policy", d.serve_policy,
                   "serve greedy actions to remote actors "
                   "(--policy_port) via the continuous-batching "
                   "policy server")
    p.add_argument("--serve_policy_port", type=int,
                   default=d.serve_policy_port)
    p.add_argument("--serve_policy_window_s", type=float,
                   default=d.serve_policy_window_s,
                   help="continuous-batching window: the first pending "
                        "request waits at most this long for riders")
    p.add_argument("--serve_policy_max_rows", type=int,
                   default=d.serve_policy_max_rows,
                   help="row budget per fused serving dispatch")
    p.add_argument("--serve_policy_sla_s", type=float,
                   default=d.serve_policy_sla_s,
                   help="declared params-freshness SLA: batches served "
                        "from an older snapshot count sla_breaches")
    _add_bool_flag(p, "autoscale", d.autoscale,
                   "run the obs-driven autoscaler (elastic/autoscaler): "
                   "live-adjust serving batch limits, ingest depth, "
                   "dealer pacing and active replica count from "
                   "registry signals, every decision ledgered")
    p.add_argument("--autoscale_interval_s", type=float,
                   default=d.autoscale_interval_s,
                   help="autoscaler control-loop period")
    p.add_argument("--weight_window", type=int, default=d.weight_window,
                   help="weight-broadcast delta window: recent versions "
                        "kept server-side so in-window pullers get "
                        "per-tensor deltas instead of full snapshots")
    p.add_argument("--ingest_shards", type=int, default=d.ingest_shards,
                   help="receiver-side ingest shards: K SO_REUSEPORT "
                        "listeners + K decode/stage workers + one ordered "
                        "merge-commit thread (1 = legacy single drain)")
    p.add_argument("--trace_sample", type=float, default=d.trace_sample,
                   help="arm wire-to-grad trace spans (obs/trace): the "
                        "learner records per-stage latency histograms for "
                        "frames remote actors sample at this rate over "
                        "the raw codec (0 = off)")
    p.add_argument("--learners", type=int, default=d.learners,
                   help="learner replicas: N>1 runs each on its own "
                        "thread against the shared replay service, with "
                        "an aggregator merging their updates into the "
                        "single versioned weight stream (1 = legacy "
                        "fused single-learner loop)")
    p.add_argument("--agg_mode", choices=("async", "sync"),
                   default=d.agg_mode,
                   help="update aggregation: 'async' = IMPACT-style "
                        "clipped staleness-weighted correction, 'sync' = "
                        "N-way averaging barrier")
    p.add_argument("--agg_clip", type=float, default=d.agg_clip,
                   help="staleness-weight clip (async mode): a stale "
                        "update's weight is max(1/(1+lag), 1/clip)")
    p.add_argument("--agg_transport", choices=("auto", "socket", "collective"),
                   default=d.agg_transport,
                   help="how replica updates reach the merge: "
                        "'collective' = mesh-native on-device merge over "
                        "the 'replica' mesh axis (replicas share one "
                        "single-host mesh), 'socket' = host-thread "
                        "aggregator over 0xD4AB frames (cross-host "
                        "fallback), 'auto' = collective when a mesh is "
                        "present and single-host")
    _add_bool_flag(p, "sample_on_ingest", d.sample_on_ingest,
                   "fuse PER sampling into the receive path: the commit "
                   "thread deals ready-to-train blocks to the learner "
                   "replicas (host replay + prioritized only)")
    p.add_argument("--sampler", choices=("auto", "scan", "host"),
                   default=d.sampler,
                   help="sample-path arm for --sample_on_ingest: 'scan' = "
                        "device gather descent fused behind the commit "
                        "dispatch, 'host' = PR-12 host SampleDealer, "
                        "'auto' = scan on a TPU, host elsewhere")
    p.add_argument("--profile_dir", default=d.profile_dir)
    p.add_argument("--log_dir", default=d.log_dir)
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--reward_scale", type=float, default=d.reward_scale)
    _add_bool_flag(p, "checkpoint_replay", d.checkpoint_replay,
                   "include the replay buffer in checkpoints")
    p.add_argument("--checkpoint_replay_every", type=int,
                   default=d.checkpoint_replay_every)
    _add_bool_flag(p, "resume", d.resume, "resume from latest checkpoint")
    _add_bool_flag(p, "strict_reference", d.strict_reference,
                   "reference hyperparameter parity mode")
    return p


def parse_args(argv=None) -> ExperimentConfig:
    ns = vars(build_parser().parse_args(argv))
    ns["her"] = bool(ns["her"])
    ns["prioritized_replay"] = bool(ns.pop("p_replay"))
    ns["resume"] = bool(ns["resume"])
    ns["checkpoint_replay"] = bool(ns["checkpoint_replay"])
    ns["async_actors"] = bool(ns["async_actors"])
    ns["serve"] = bool(ns["serve"])
    ns["serve_policy"] = bool(ns["serve_policy"])
    ns["concurrent_eval"] = bool(ns["concurrent_eval"])
    ns["strict_reference"] = bool(ns["strict_reference"])
    ns["normalize_obs"] = bool(ns["normalize_obs"])
    ns["sample_on_ingest"] = bool(ns["sample_on_ingest"])
    ns["autoscale"] = bool(ns["autoscale"])
    return ExperimentConfig(**ns)
