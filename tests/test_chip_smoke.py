"""``chip_smoke.py``'s body at a tiny size on the CPU: ``--platform cpu``,
the ``point`` env and the same assertions, so the smoke's control flow is
exercised here before chip time is spent on it.
The full-width run needs the chip (``python chip_smoke.py`` through the
chip tool); that it REFUSES to run without one is pinned in
``tests/test_startup.py``."""

import importlib.util
import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_body_passes_at_tiny_size_on_cpu(tmp_path, monkeypatch):
    # placed from outside, so startup.configure sets no directory in code
    # and this in-process run leaves the in-checkout cache untouched
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    smoke = _load_smoke()
    assert smoke.run(smoke.TINY, "cpu", str(tmp_path)) == []


def test_smoke_checks_catch_each_miss(tmp_path):
    """``check_train`` on a doctored result: every assertion the chip run
    relies on actually fires."""
    smoke = _load_smoke()
    size = smoke.TINY
    steps = size["n_cycles"] * size["train_steps_per_cycle"]
    os.makedirs(tmp_path / "ckpt" / str(steps))
    good = {
        "learner_step": steps, "critic_loss": 0.1, "actor_loss": 1.0,
        "avg_test_reward": -3.0, "compiles_by_cycle": [4, 0, 0],
        "plan": {"storage": "device", "fused": True, "state_on": "cpu",
                 "ring_on": "cpu", "K": size["updates_per_dispatch"]},
    }
    check = lambda r, crashes=0: smoke.check_train(
        r, size, "cpu", str(tmp_path), crashes)
    assert check(good) == []
    assert len(check(good, crashes=1)) == 1
    no_eval = {k: v for k, v in good.items() if k != "avg_test_reward"}
    assert len(check(no_eval)) == 1
    assert len(check({**good, "learner_step": steps - 1})) == 1
    assert len(check({**good, "critic_loss": float("nan")})) == 1
    assert len(check({**good, "compiles_by_cycle": [4, 0, 1]})) == 1
    host = {"storage": "host", "fused": False, "state_on": "cpu",
            "K": size["updates_per_dispatch"]}  # a host plan has no ring_on
    assert len(check({**good, "plan": host})) == 2
    assert len(smoke.check_train(good, size, "cpu", str(tmp_path / "x"),
                                 0)) == 1  # no checkpoint there
