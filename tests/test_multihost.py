"""Simulated multi-host: two local processes form one jax.distributed mesh
and run the sharded D4PG update (SURVEY.md §4; VERDICT r1 #8). Spawned as
real subprocesses — jax.distributed state is process-global and must not
contaminate the test process.

Backend support is PROBED, not assumed (mirroring test_native.py's
loader-skip pattern): some jaxlib builds cannot run multiprocess
computations on the CPU backend at all ("Multiprocess computations
aren't implemented on the CPU backend" out of every collective), which
previously failed all of this module identically on such containers. A
tiny two-process ``jax.distributed`` barrier runs once per session; when
it dies, every test here SKIPS with the probe's error as the reason.
The probe is lazy (module-scoped fixture), so merely collecting this
``slow``-marked module costs nothing in a ``-m "not slow"`` tier-1 run.
"""

import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


_PROBE_SRC = """
import jax
jax.config.update("jax_platforms", "cpu")
import sys
jax.distributed.initialize(coordinator_address=sys.argv[1],
                           num_processes=2, process_id=int(sys.argv[2]))
from jax.experimental import multihost_utils
multihost_utils.sync_global_devices("probe")
print("MULTIHOST_PROBE_OK")
"""


def _probe_multiprocess_backend() -> tuple[bool, str]:
    """Can this jax/jaxlib actually run a two-process CPU collective?"""
    port = _free_port()
    env = _mh_env()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _PROBE_SRC,
             f"127.0.0.1:{port}", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            p.kill()
            out = "probe timeout"
        outs.append(out)
    if all(p.returncode == 0 for p in procs) and all(
            "MULTIHOST_PROBE_OK" in out for out in outs):
        return True, ""
    # surface the terminal error line as the skip reason
    reason = "multiprocess jax probe failed"
    for out in outs:
        for line in reversed(out.splitlines()):
            if "Error" in line or "error" in line:
                reason = line.strip()[:200]
                break
        else:
            continue
        break
    return False, reason


@pytest.fixture(scope="module", autouse=True)
def _require_multiprocess_backend():
    ok, reason = _probe_multiprocess_backend()
    if not ok:
        pytest.skip("jax.distributed cannot run two CPU processes on "
                    f"this build: {reason}")


def _mh_env() -> dict:
    env = dict(os.environ)
    env.update({
        # explicit CPU (JAX_PLATFORMS is honoured; d4pg_tpu/startup.py
        # passes a caller-set value through): the children must never
        # take a chip
        "PYTHONPATH": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    })
    return env


def test_two_process_full_train(tmp_path):
    """The real train() CLI across two processes: PER chunk pipeline
    (global [K, B] staging + local td write-back), the single-dispatch
    remainder, per-cycle checkpointing (process 0 only owns io/ckpt/eval —
    process 1 must not crash on the absent manager)."""
    port = _free_port()
    env = _mh_env()
    args = [
        "--env", "point", "--max_steps", "20", "--num_envs", "2",
        "--warmup", "100", "--n_eps", "1", "--n_cycles", "2",
        "--episodes_per_cycle", "1", "--train_steps_per_cycle", "18",
        "--updates_per_dispatch", "8", "--eval_trials", "1",
        "--bsize", "16", "--rmsize", "2000", "--n_atoms", "11",
        "--v_min", "-5.0", "--v_max", "0.0",
        "--log_dir", str(tmp_path),
        "--coordinator", f"127.0.0.1:{port}", "--num_processes", "2",
    ]
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "d4pg_tpu.train", *args,
             "--process_id", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=600)
        outs.append(out)
        assert p.returncode == 0, out[-3000:]
    assert all("final:" in out for out in outs)
    # eval/io belong to process 0 alone
    assert "avg_test_reward" in outs[0]
    assert "avg_test_reward" not in outs[1]


def test_two_processes_form_one_mesh():
    port = _free_port()
    env = _mh_env()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "d4pg_tpu.parallel.multihost_check",
             "--coordinator", f"127.0.0.1:{port}",
             "--num_processes", "2", "--process_id", str(i)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
        assert p.returncode == 0, out[-2000:]
    oks = [line for out in outs for line in out.splitlines()
           if line.startswith("multihost_check OK")]
    assert len(oks) == 2
    assert "mesh 8 devices" in oks[0]
    # replicas agree: both processes report identical losses
    assert oks[0].split("losses")[1] == oks[1].split("losses")[1]


def test_two_processes_fused_replay_plane():
    """VERDICT r3 #1: the fused sharded replay data plane on the
    multi-host runtime — each host drains its rows into its own shard-set
    (collective insert), the fused chunk runs SPMD over the global mesh,
    and the per-host checkpoint payload roundtrips. Replica losses must
    agree bit-for-bit across processes."""
    port = _free_port()
    env = _mh_env()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "d4pg_tpu.parallel.multihost_check",
             "--coordinator", f"127.0.0.1:{port}",
             "--num_processes", "2", "--process_id", str(i), "--fused", "1"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out)
        assert p.returncode == 0, out[-2000:]
    oks = [line for out in outs for line in out.splitlines()
           if line.startswith("multihost_check OK")]
    assert len(oks) == 2
    assert oks[0].split("losses")[1] == oks[1].split("losses")[1]


def test_two_process_fused_full_train_and_resume(tmp_path):
    """The real train() CLI with --fused_replay on across two processes
    (VERDICT r3 #1's 'production configuration'): device-sharded ring +
    trees over the global mesh, collective drains at chunk boundaries,
    per-cycle checkpointing with per-host replay sidecars, then a resume
    where BOTH hosts restore their own shard-set."""
    env = _mh_env()
    base = [
        "--env", "point", "--max_steps", "20", "--num_envs", "2",
        "--warmup", "100", "--n_eps", "1", "--n_cycles", "2",
        "--episodes_per_cycle", "1", "--train_steps_per_cycle", "18",
        "--updates_per_dispatch", "8", "--eval_trials", "1",
        "--bsize", "16", "--rmsize", "2000", "--n_atoms", "11",
        "--v_min", "-5.0", "--v_max", "0.0",
        "--replay_storage", "device", "--fused_replay", "on",
        "--checkpoint_replay", "1", "--checkpoint_replay_every", "1",
        "--log_dir", str(tmp_path), "--num_processes", "2",
    ]

    def launch(extra_args):
        port = _free_port()
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "d4pg_tpu.train", *base, *extra_args,
                 "--coordinator", f"127.0.0.1:{port}",
                 "--process_id", str(i)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
        return outs

    outs = launch([])
    assert all("final:" in out for out in outs)
    # the two replicas ended on the SAME loss (losses are printed in the
    # final dict; replica divergence would show up here)
    finals = [out.rsplit("final:", 1)[1].split("critic_loss': ")[1]
                 .split(",")[0] for out in outs]
    assert finals[0] == finals[1], finals
    # EVERY host wrote its replay shard sidecar, process 0 included
    run_dirs = [d for d in os.listdir(tmp_path) if d.startswith("exp_")]
    assert len(run_dirs) == 1
    assert os.path.exists(os.path.join(tmp_path, run_dirs[0], "replay_p0.pkl"))
    assert os.path.exists(os.path.join(tmp_path, run_dirs[0], "replay_p1.pkl"))

    outs = launch(["--resume", "1"])
    import re

    for i, out in enumerate(outs):
        assert f"[p{i}] resumed from step 36" in out, out[-3000:]
    rows = [int(re.search(r"(\d+) replay rows", out).group(1))
            for out in outs]
    assert all(r > 0 for r in rows), rows


def test_two_process_resume_with_normalize(tmp_path):
    """VERDICT r2 #6: the multi-host runtime must support --resume and
    --normalize_obs. Run 1 trains with synced observation normalization
    and per-cycle checkpoints (replay snapshots every save: process 0's in
    the Orbax extra, process 1's as a sidecar file). Run 2 resumes: BOTH
    processes must restore the broadcast state, their own replay shard,
    and the shared normalizer statistics."""
    env = _mh_env()
    base = [
        "--env", "point", "--max_steps", "20", "--num_envs", "2",
        "--warmup", "100", "--n_eps", "1", "--n_cycles", "2",
        "--episodes_per_cycle", "1", "--train_steps_per_cycle", "8",
        "--updates_per_dispatch", "4", "--eval_trials", "1",
        "--bsize", "16", "--rmsize", "2000", "--n_atoms", "11",
        "--v_min", "-5.0", "--v_max", "0.0",
        "--normalize_obs", "1", "--checkpoint_replay", "1",
        "--checkpoint_replay_every", "1",
        "--log_dir", str(tmp_path), "--num_processes", "2",
    ]

    def launch(extra_args):
        port = _free_port()
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "d4pg_tpu.train", *base, *extra_args,
                 "--coordinator", f"127.0.0.1:{port}",
                 "--process_id", str(i)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
            assert p.returncode == 0, out[-3000:]
        return outs

    launch([])
    # both hosts wrote their replay shard (p0 via Orbax extra, p1 sidecar)
    run_dirs = [d for d in os.listdir(tmp_path) if d.startswith("exp_")]
    assert len(run_dirs) == 1
    assert os.path.exists(os.path.join(tmp_path, run_dirs[0], "replay_p1.pkl"))

    outs = launch(["--resume", "1"])
    for i, out in enumerate(outs):
        assert f"[p{i}] resumed from step 16" in out, out[-3000:]
    # resumed replay shards were non-empty on both hosts
    import re

    rows = [int(re.search(r"(\d+) replay rows", out).group(1)) for out in outs]
    assert all(r > 0 for r in rows), rows
