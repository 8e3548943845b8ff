"""Scripted multi-host check: N local processes form ONE mesh and run the
full sharded D4PG update (SURVEY.md §4 "multi-host tests via
jax.distributed-under-simulation"; VERDICT r1 #8).

Every process runs this same program (SPMD), e.g. for two processes:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python -m d4pg_tpu.parallel.multihost_check \
        --coordinator 127.0.0.1:29781 --num_processes 2 --process_id 0 &
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      python -m d4pg_tpu.parallel.multihost_check \
        --coordinator 127.0.0.1:29781 --num_processes 2 --process_id 1

Each process contributes its local virtual CPU devices, samples its OWN
local half of the global batch, and the jit'd update all-reduces gradients
across the 8-device global mesh. Success prints ``multihost_check OK`` on
every process with the same loss (replicas agree bit-for-bit).
"""

from __future__ import annotations

import argparse
from functools import partial

import numpy as np


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="d4pg_tpu.parallel.multihost_check")
    ap.add_argument("--coordinator", required=True)
    ap.add_argument("--num_processes", type=int, required=True)
    ap.add_argument("--process_id", type=int, required=True)
    ap.add_argument("--cpu", type=int, default=1,
                    help="force the CPU backend (simulation mode)")
    ap.add_argument("--fused", type=int, default=0,
                    help="exercise the sharded fused replay data plane "
                         "(replay/sharded_per.py + learner/fused.py) "
                         "instead of the host-batch sharded update")
    ns = ap.parse_args(argv)

    import jax

    if ns.cpu:
        jax.config.update("jax_platforms", "cpu")

    from d4pg_tpu.parallel import multihost

    multihost.initialize(ns.coordinator, ns.num_processes, ns.process_id)
    assert jax.process_count() == ns.num_processes

    from d4pg_tpu.learner import D4PGConfig, init_state, make_update
    from d4pg_tpu.replay.uniform import TransitionBatch

    mesh = multihost.global_mesh()
    n_global = len(jax.devices())
    obs_dim, act_dim = 6, 2
    local_b = 2 * len(jax.local_devices())

    config = D4PGConfig(obs_dim=obs_dim, act_dim=act_dim, v_min=-5.0,
                        v_max=0.0, n_atoms=11, hidden=(16, 16))
    # identical seed on every process -> identical replicated state
    state = multihost.replicate_state_global(
        partial(init_state, config, jax.random.key(0)), mesh)
    update = make_update(config, mesh=mesh, donate=True)

    # each process samples ITS shard of the global batch
    rng = np.random.default_rng(100 + ns.process_id)
    done = np.zeros(local_b, np.float32)
    local = TransitionBatch(
        obs=rng.standard_normal((local_b, obs_dim)).astype(np.float32),
        action=rng.uniform(-1, 1, (local_b, act_dim)).astype(np.float32),
        reward=rng.standard_normal(local_b).astype(np.float32),
        next_obs=rng.standard_normal((local_b, obs_dim)).astype(np.float32),
        done=done,
        discount=(0.99 * (1.0 - done)).astype(np.float32),
    )
    losses = []
    if ns.fused:
        # The fused sharded replay data plane across hosts: each host
        # drains ITS rows into its local shards (collective insert), then
        # both run the fused chunk — sample + update + priority write-back
        # all inside one SPMD dispatch over the global mesh.
        from d4pg_tpu.learner.fused import make_fused_chunk
        from d4pg_tpu.replay.sharded_per import ShardedFusedReplay

        buf = ShardedFusedReplay(256, obs_dim, act_dim, mesh, alpha=0.6)
        for _ in range(4):
            buf.add(local)
            buf.drain()
        fn = make_fused_chunk(config, mesh=mesh, k=2, batch_size=16,
                              alpha=0.6, donate=False)
        trees = buf.trees
        for _ in range(2):
            state, trees, metrics = fn(state, trees, buf.storage, buf.size)
            losses.append(float(jax.device_get(metrics["critic_loss"][-1])))
        # per-host checkpoint payload survives a roundtrip into a fresh
        # buffer (the multi-host sidecar resume path)
        buf.trees = trees
        snap = buf.state_dict()
        buf2 = ShardedFusedReplay(256, obs_dim, act_dim, mesh, alpha=0.6)
        buf2.load_state_dict(snap)
        assert len(buf2) == len(buf) > 0
        assert int(jax.device_get(state.step)) == 4
    else:
        for _ in range(2):
            batch = multihost.make_global_batch(local, mesh)
            state, metrics = update(state, batch, None)
            losses.append(float(jax.device_get(metrics["critic_loss"])))
        assert int(jax.device_get(state.step)) == 2
    assert all(np.isfinite(losses))
    print(
        f"multihost_check OK: process {ns.process_id}/{ns.num_processes}, "
        f"mesh {n_global} devices "
        f"({len(jax.local_devices())} local), losses {losses[0]:.6f} "
        f"{losses[1]:.6f}",
        flush=True,
    )


if __name__ == "__main__":
    main()
