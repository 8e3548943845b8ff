"""``dryrun_multichip`` is a virtual-mesh correctness check and must never
initialize a non-CPU backend: one process holds the chip at a time, and a
driver that runs the dry-run beside a chip job must not contend for it.

Run in a subprocess: backend selection is process-global state, and the
point is to exercise the real driver code path with NO prior CPU pinning
(no conftest config.update active in the child).
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The child deliberately does NOT set JAX_PLATFORMS / pin CPU beforehand:
# dryrun_multichip itself must do the forcing. Afterwards, the set of
# *initialized* backends (xla_bridge's process-global registry) must be
# exactly {cpu} — i.e. the accelerator plugin was never touched, even
# though it stays visible to the process.
_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
os.environ.pop("JAX_PLATFORMS", None)
import __graft_entry__
__graft_entry__.dryrun_multichip({n})
from jax._src import xla_bridge
initialized = set(xla_bridge._backends)
assert initialized == {{"cpu"}}, f"non-CPU backend initialized: {{initialized}}"
print("BACKENDS-OK", sorted(initialized))
"""


@pytest.mark.slow
def test_dryrun_multichip_never_initializes_accelerator():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = _REPO
    r = subprocess.run(
        [sys.executable, "-c", _CHILD.format(n=4)],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert "BACKENDS-OK ['cpu']" in r.stdout
    assert "dryrun_multichip OK" in r.stdout
    assert "dryrun multihost fused OK" in r.stdout
