"""Config/CLI, metrics, and checkpoint/resume tests."""

import csv
import os

import jax
import numpy as np
import pytest

from d4pg_tpu.config import ExperimentConfig, parse_args
from d4pg_tpu.io import CheckpointManager, CsvLogger, MetricsBus
from d4pg_tpu.learner import D4PGConfig, init_state, make_update
from d4pg_tpu.replay.uniform import TransitionBatch


def test_parse_args_defaults_and_overrides():
    cfg = parse_args([])
    assert cfg.env == "Pendulum-v1" and cfg.prioritized_replay and not cfg.her
    cfg = parse_args(["--env", "point", "--p_replay", "0", "--her", "1",
                      "--bsize", "128", "--rmsize", "999", "--n_eps", "3",
                      "--adam_b2", "0.9"])
    assert cfg.env == "point" and not cfg.prioritized_replay and cfg.her
    assert cfg.batch_size == 128 and cfg.memory_size == 999
    assert cfg.n_epochs == 3 and cfg.adam_b2 == 0.9


def test_run_name_encodes_config():
    """Parity with the reference's run-dir naming (main.py:59-64)."""
    cfg = ExperimentConfig(env="Pendulum-v1", prioritized_replay=True, her=False,
                           n_steps=3, n_workers=2)
    name = cfg.run_name()
    assert "Pendulum-v1" in name and "PER" in name and "HER" not in name
    assert "3N" in name and "2Workers" in name


def test_preset_resolution():
    cfg = ExperimentConfig(env="Pendulum-v1").resolve()
    assert cfg.v_min == -100.0 and cfg.v_max == 0.0 and cfg.reward_scale == 0.1
    # explicit values win over presets
    cfg = ExperimentConfig(env="Pendulum-v1", v_min=-7.0, v_max=7.0).resolve()
    assert cfg.v_min == -7.0 and cfg.v_max == 7.0


def test_csv_logger(tmp_path):
    path = str(tmp_path / "returns.csv")
    log = CsvLogger(path, ["a", "b"])
    log.write(1, {"a": 1.5, "b": 2.5})
    log.write(2, {"a": 3.0})
    log.close()
    rows = list(csv.reader(open(path)))
    assert rows[0] == ["1", "1.5", "2.5"]
    assert rows[1] == ["2", "3.0", ""]


def test_metrics_bus_fanout(tmp_path):
    got = []

    class Sink:
        def write(self, step, metrics):
            got.append((step, dict(metrics)))

        def close(self):
            pass

    bus = MetricsBus([Sink()])
    bus.log(3, {"x": 1.0})
    bus.close()
    assert got == [(3, {"x": 1.0})]


def test_checkpoint_roundtrip_and_resume(tmp_path, rng):
    """Full-state save -> restore -> identical params AND identical
    continued training (the resume capability the reference lacks, C20)."""
    config = D4PGConfig(obs_dim=3, act_dim=1, v_min=-5, v_max=0, n_atoms=11,
                        hidden=(16, 16))
    state = init_state(config, jax.random.key(0))
    update = make_update(config, donate=False)
    done = np.zeros(8, np.float32)
    batch = TransitionBatch(
        obs=rng.standard_normal((8, 3)).astype(np.float32),
        action=rng.uniform(-1, 1, (8, 1)).astype(np.float32),
        reward=rng.standard_normal(8).astype(np.float32),
        next_obs=rng.standard_normal((8, 3)).astype(np.float32),
        done=done,
        discount=(0.99 * (1 - done)).astype(np.float32),
    )
    for _ in range(3):
        state, _ = update(state, batch, None)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(state, extra={"env_steps": 123})
    mgr.wait()
    assert mgr.latest_step == 3

    template = init_state(config, jax.random.key(99))
    restored, extra = mgr.restore(template)
    assert extra["env_steps"] == 123
    assert int(restored.step) == 3
    for a, b in zip(jax.tree_util.tree_leaves(state.actor_params),
                    jax.tree_util.tree_leaves(restored.actor_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # continued training from the restore matches continued training live
    s_live, _ = update(state, batch, None)
    s_resumed, _ = update(restored, batch, None)
    for a, b in zip(jax.tree_util.tree_leaves(s_live.critic_params),
                    jax.tree_util.tree_leaves(s_resumed.critic_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


def test_replay_state_roundtrip_host_and_per(rng):
    """Replay checkpointing (SURVEY.md §5 elastic recovery): contents,
    ring cursor, PER leaf priorities and max_priority all survive a
    state_dict round trip — on the host buffer and the fused device
    buffer alike."""
    from d4pg_tpu.replay import PrioritizedReplayBuffer
    from d4pg_tpu.replay.fused_buffer import FusedDeviceReplay

    def fill(buf):
        done = np.zeros(40, np.float32)
        buf.add(TransitionBatch(
            obs=rng.standard_normal((40, 3)).astype(np.float32),
            action=rng.uniform(-1, 1, (40, 1)).astype(np.float32),
            reward=np.arange(40, dtype=np.float32),
            next_obs=rng.standard_normal((40, 3)).astype(np.float32),
            done=done,
            discount=np.full(40, 0.99, np.float32)))

    src = PrioritizedReplayBuffer(64, 3, 1, alpha=0.6)
    fill(src)
    src.update_priorities(np.arange(10), np.linspace(1, 5, 10))
    dst = PrioritizedReplayBuffer(64, 3, 1, alpha=0.6)
    dst.load_state_dict(src.state_dict())
    assert dst.size == src.size and dst.head == src.head
    np.testing.assert_array_equal(dst.reward[:40], src.reward[:40])
    np.testing.assert_allclose(dst._trees.get(np.arange(40)),
                               src._trees.get(np.arange(40)))
    assert dst.max_priority == src.max_priority
    # min tree of unwritten slots stays neutral: sampling still works
    assert np.isfinite(dst.is_weights(np.arange(5), 0.5)).all()

    fsrc = FusedDeviceReplay(64, 3, 1, alpha=0.6)
    fill(fsrc)
    fsrc.drain()
    fdst = FusedDeviceReplay(64, 3, 1, alpha=0.6)
    fdst.load_state_dict(fsrc.state_dict())
    assert fdst.size == 40 and fdst.head == fsrc.head
    np.testing.assert_array_equal(np.asarray(fdst.storage.reward[:40]),
                                  np.asarray(fsrc.storage.reward[:40]))
    np.testing.assert_allclose(np.asarray(fdst.trees.sum_tree),
                               np.asarray(fsrc.trees.sum_tree))


def test_train_resume_with_replay(tmp_path):
    """--checkpoint_replay 1 + --resume 1: the second run restores the
    buffer (no re-warmup) and continues from the checkpointed step."""
    from d4pg_tpu.train import train

    common = dict(
        env="point", max_steps=20, num_envs=2, warmup=100, n_epochs=1,
        n_cycles=2, episodes_per_cycle=1, train_steps_per_cycle=4,
        eval_trials=1, batch_size=16, memory_size=2000,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-5.0, v_max=0.0, checkpoint_replay=True,
        checkpoint_replay_every=1,
    )
    m1 = train(ExperimentConfig(**common))
    m2 = train(ExperimentConfig(**common, resume=True))
    assert np.isfinite(m2["critic_loss"])
    assert m2["env_steps"] > m1["env_steps"]
    # the restored buffer skips the second warmup: only the two collect
    # phases (~80 env steps) are added, not another ~100-step warmup
    assert m2["env_steps"] - m1["env_steps"] < 100


def test_checkpoint_restore_empty_dir(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "empty"))
    config = D4PGConfig(obs_dim=3, act_dim=1, n_atoms=11, hidden=(8,))
    with pytest.raises(FileNotFoundError):
        mgr.restore(init_state(config, jax.random.key(0)))
    mgr.close()


def test_train_entrypoint_end_to_end(tmp_path):
    """Tiny full run through the CLI path on the fake env (no MuJoCo)."""
    from d4pg_tpu.train import train

    cfg = ExperimentConfig(
        env="point", max_steps=20, num_envs=2, warmup=100, n_epochs=1,
        n_cycles=1, episodes_per_cycle=1, train_steps_per_cycle=3,
        eval_trials=1, batch_size=16, memory_size=2000,
        log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
        v_min=-5.0, v_max=0.0,
    )
    metrics = train(cfg)
    assert "avg_test_reward" in metrics and np.isfinite(metrics["critic_loss"])
    run_dir = os.path.join(str(tmp_path), cfg.run_name())
    assert os.path.exists(os.path.join(run_dir, "returns.csv"))
    assert os.path.isdir(os.path.join(run_dir, "ckpt"))


def test_full_train_determinism(tmp_path):
    """System-level determinism (SURVEY.md §5): two identical sync-mode
    runs produce identical eval trajectories — the property the reference's
    hogwild design cannot have."""
    from d4pg_tpu.train import train

    def run(tag):
        # concurrent_eval=False: with the background evaluator, WHICH cycle
        # row an eval result lands in depends on thread timing; inline eval
        # keeps the CSV bitwise-reproducible.
        cfg = ExperimentConfig(
            env="point", max_steps=20, num_envs=2, warmup=100, n_epochs=1,
            n_cycles=2, episodes_per_cycle=2, train_steps_per_cycle=4,
            eval_trials=2, batch_size=16, memory_size=2000,
            log_dir=str(tmp_path / tag), hidden=(16, 16), n_atoms=11,
            v_min=-5.0, v_max=0.0, seed=123, concurrent_eval=False,
        )
        train(cfg)
        csv = os.path.join(str(tmp_path / tag), cfg.run_name(), "returns.csv")
        return open(csv).read()

    assert run("a") == run("b")


def test_strict_reference_mode():
    """--strict_reference 1 = the reference's own hyperparameters in one
    flag (VERDICT r1 #10)."""
    from d4pg_tpu.config import parse_args

    cfg = parse_args(["--env", "Pendulum-v1", "--strict_reference", "1"]).resolve()
    assert cfg.v_min == -300.0 and cfg.v_max == 0.0  # main.py:86-88
    assert cfg.reward_scale == 1.0
    assert cfg.adam_b1 == 0.9 and cfg.adam_b2 == 0.9  # shared_adam.py:4
    assert cfg.lr_actor == 1e-3 and cfg.lr_critic == 1e-3
    assert cfg.updates_per_dispatch == 1
    # default mode keeps the documented divergence
    d = parse_args(["--env", "Pendulum-v1"]).resolve()
    assert d.v_min == -100.0 and d.reward_scale == 0.1


def test_host_replay_sidecar_staleness_rules(tmp_path):
    """The step-stamped replay sidecar: an OLDER snapshot than the
    restored state is accepted (stale rows are valid experience; the old
    strict-equality rule emptied the buffer whenever the replay cadence
    was coarser than the state cadence), a NEWER one is refused (the
    save site commits state before the sidecar rename, so ahead-of-state
    means mixed run dirs)."""
    from d4pg_tpu.train import _load_host_replay, _save_host_replay

    snap = {"rows": "payload"}
    _save_host_replay(str(tmp_path), 0, step=100, snap=snap)
    # exact match
    got, step = _load_host_replay(str(tmp_path), 0, step=100)
    assert got == snap and step == 100
    # stale (older than state): accepted
    got, step = _load_host_replay(str(tmp_path), 0, step=160)
    assert got == snap and step == 100
    # ahead of state: refused
    got, step = _load_host_replay(str(tmp_path), 0, step=40)
    assert got is None and step == -1
    # absent
    got, step = _load_host_replay(str(tmp_path), 7, step=100)
    assert got is None and step == -1


def test_single_host_resume_reads_stale_sidecar(tmp_path):
    """Resume restores the buffer from the sidecar even when the replay
    cadence was coarser than the state cadence — the round-4 failure
    mode: the LATEST state checkpoint used to be the only replay source,
    so 4 out of 5 resumes silently restarted with an empty buffer."""
    import re

    from d4pg_tpu.config import ExperimentConfig
    from d4pg_tpu.train import train

    def run(resume):
        cfg = ExperimentConfig(
            env="point", max_steps=20, num_envs=2, warmup=100, n_epochs=1,
            n_cycles=4, episodes_per_cycle=1, train_steps_per_cycle=8,
            eval_trials=1, batch_size=16, memory_size=2000,
            log_dir=str(tmp_path), hidden=(16, 16), n_atoms=11,
            v_min=-5.0, v_max=0.0, checkpoint_replay=True,
            # replay saved only every 3rd save; state saved every cycle —
            # the LAST state checkpoint (cycle 4) has no replay save
            checkpoint_replay_every=3, resume=resume,
        )
        return train(cfg)

    run(False)
    run_dirs = [d for d in os.listdir(tmp_path) if d.startswith("exp_")]
    sidecar = os.path.join(tmp_path, run_dirs[0], "replay_p0.pkl")
    assert os.path.exists(sidecar)
    import io as _io
    from contextlib import redirect_stdout

    buf = _io.StringIO()
    with redirect_stdout(buf):
        run(True)
    out = buf.getvalue()
    m = re.search(r"resumed from step (\d+) \((\d+) env steps, (\d+) replay rows", out)
    assert m, out[-2000:]
    assert int(m.group(1)) == 32  # restored latest state (4 cycles x 8)
    assert int(m.group(3)) > 0   # buffer restored from the STALE sidecar
    assert "steps behind the restored state" in out
