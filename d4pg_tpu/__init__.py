"""d4pg_tpu — a TPU-native distributed distributional DDPG (D4PG) framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
``ajgupta93/d4pg-pytorch`` (reference mounted at /root/reference):

- categorical (C51-style) distributional critic with configurable value support,
  plus a real mixture-of-Gaussian critic head (a stub in the reference,
  ``models.py:63-65``),
- categorical Bellman projection as an MXU-friendly one-hot interpolation
  matmul (replacing host-side numpy loops, reference ``ddpg.py:142-185``),
- uniform and prioritized replay (vectorized segment trees + optional C++
  native sampler), n-step returns, HER,
- Gaussian / Ornstein-Uhlenbeck exploration with PRNG-key discipline,
- a single jit'd learner update (losses, grads, Adam, soft target update in
  one XLA computation), data-parallel over a ``jax.sharding.Mesh`` via
  ``shard_map`` + ``psum`` over ICI,
- actor/evaluator/replay services for distributed actor fan-out,
- typed config, TensorBoard metrics, Orbax checkpoint/resume, plotting CLI.

See SURVEY.md for the reference analysis this build follows.
"""

# The first statements of the first module of the package an entry point
# imports: the epoch of the start-up log (``obs/startup_log.py``; stdlib
# only), and the hook that times first imports from here on.
import time as _time

_EPOCH = _time.monotonic()

from d4pg_tpu.obs import startup_log as _startup_log  # noqa: E402

_startup_log.open_log(_EPOCH)

__version__ = "0.1.0"
