"""The one backend rule (``d4pg_tpu/startup.py``): the chip unless the CPU
was asked for, never a fallback; the compile cache placed from outside or
at one fixed path in the checkout; CPU inference devices still resolvable
under the chip rule; and entry points that refuse to run without a chip.

Backend selection is process-global, so whatever initialises a backend
runs in a subprocess; the rule itself is unit-checked in-process by
recording what ``configure`` sets.
"""

import glob
import os
import subprocess
import sys

import jax
import pytest

from d4pg_tpu import startup

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the refusal tests assert what happens WITHOUT a chip; on a machine that
# has one the same commands would (rightly) train on it
_no_chip = pytest.mark.skipif(
    bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*")),
    reason="a TPU is attached: the chip-less refusal cannot be observed")


def _run(code_or_args, *, env_extra=None, cwd=_REPO, timeout=240):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "XLA_FLAGS")}
    env["PYTHONPATH"] = _REPO
    env.update(env_extra or {})
    args = (["-c", code_or_args] if isinstance(code_or_args, str)
            else list(code_or_args))
    return subprocess.run([sys.executable, *args], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


@pytest.fixture
def recorded(monkeypatch):
    """What ``configure`` asks jax to set, without setting it (the test
    process is already pinned to the CPU by conftest)."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    return calls


@_no_chip
def test_chip_request_without_a_chip_fails_and_does_not_train(tmp_path):
    r = _run(["-m", "d4pg_tpu.train", "--env", "point", "--warmup", "50",
              "--n_eps", "1", "--n_cycles", "1", "--log_dir", str(tmp_path)])
    assert r.returncode != 0
    assert "Unable to initialize backend 'tpu'" in r.stderr
    # it never reached the loop, on any backend
    assert "warmup done" not in r.stdout and "plan:" not in r.stdout


def test_cpu_by_flag_and_by_caller_environment():
    show = ("from d4pg_tpu import startup; import jax; "
            "startup.start({!r}); print('BACKEND', jax.default_backend())")
    flag = _run(show.format("cpu"))
    assert flag.returncode == 0, flag.stderr
    assert "BACKEND cpu" in flag.stdout
    assert "[startup] platform=cpu" in flag.stdout
    env = _run(show.format("tpu"), env_extra={"JAX_PLATFORMS": "cpu"})
    assert env.returncode == 0, env.stderr
    assert "BACKEND cpu" in env.stdout


def test_rule_sets_tpu_then_cpu_unless_cpu_was_asked_for(recorded,
                                                        monkeypatch):
    startup.configure()
    assert recorded["jax_platforms"] == "tpu,cpu"  # not 'tpu' alone
    startup.configure("cpu")
    assert recorded["jax_platforms"] == "cpu"
    # a caller-set list is honoured; cpu is appended when it lacks it
    for asked, want in (("cpu", "cpu"), ("tpu", "tpu,cpu"),
                        ("tpu,cpu", "tpu,cpu")):
        monkeypatch.setenv("JAX_PLATFORMS", asked)
        startup.configure()
        assert recorded["jax_platforms"] == want
    with pytest.raises(ValueError):
        startup.configure("auto")


def test_actor_device_cpu_resolves_under_every_shape_of_the_rule(
        recorded, monkeypatch):
    """Actor/evaluator inference pins ``jax.local_devices(backend='cpu')``
    — registered only when ``cpu`` is in the platform list. The rule keeps
    it there for every request shape, so ``actor_device='cpu'`` resolves
    under ``tpu,cpu`` as it does here."""
    from d4pg_tpu.serving.client import resolve_act_device

    for asked in (None, "tpu", "tpu,cpu", "cpu"):
        if asked is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", asked)
        startup.configure()
        assert "cpu" in recorded["jax_platforms"].split(",")
    assert resolve_act_device("cpu").platform == "cpu"
    assert resolve_act_device("default") is None


def test_cache_dir_is_placed_from_outside_when_set(recorded, monkeypatch,
                                                   tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    startup.configure()
    assert "jax_compilation_cache_dir" not in recorded  # jax reads the env
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    startup.configure()
    assert recorded["jax_compilation_cache_dir"] == startup.CACHE_DIR


def test_cache_dir_is_one_fixed_path_in_the_checkout(tmp_path):
    show = ("from d4pg_tpu import startup; import jax; "
            "startup.configure('cpu'); "
            "print('CACHE', jax.config.jax_compilation_cache_dir)")
    paths = []
    for cwd in (_REPO, str(tmp_path)):  # two processes, two working dirs
        r = _run(show, cwd=cwd)
        assert r.returncode == 0, r.stderr
        paths.append(r.stdout.split("CACHE ", 1)[1].strip())
    assert paths[0] == paths[1] == os.path.join(_REPO, ".jax_cache")
    assert os.path.isabs(paths[0])
    # and a caller-set directory wins, untouched by the code
    r = _run(show, env_extra={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.stdout.split("CACHE ", 1)[1].strip() == str(tmp_path)


def test_chip_smoke_refuses_the_cpu_and_a_bare_directory(tmp_path):
    """The driver's contract for ``chip_smoke.py``: a non-zero exit and no
    result line when there is no accelerator, and when the script stands
    alone without the package."""
    cpu = _run([os.path.join(_REPO, "chip_smoke.py")],
               env_extra={"JAX_PLATFORMS": "cpu"})
    assert cpu.returncode != 0
    assert '"ok"' not in cpu.stdout
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(os.path.join(_REPO, "chip_smoke.py"), "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    bare = subprocess.run([sys.executable, str(alone)], env=env,
                          cwd=str(tmp_path), capture_output=True, text=True,
                          timeout=120)
    assert bare.returncode != 0
    assert '"ok"' not in bare.stdout


def test_importing_the_entry_modules_initialises_no_backend():
    """``run_local_actor_process`` and ``actor_main.main`` pin the CPU as
    their first jax call; that only holds the chip back if nothing they
    import has initialised a backend already."""
    r = _run("import d4pg_tpu.actor_main, d4pg_tpu.train, d4pg_tpu.startup\n"
             "import d4pg_tpu.fleet.sender, d4pg_tpu.fleet.harness\n"
             "from jax._src import xla_bridge\n"
             "assert not xla_bridge.backends_are_initialized()\n"
             "print('NO-BACKEND')")
    assert r.returncode == 0, r.stderr
    assert "NO-BACKEND" in r.stdout
