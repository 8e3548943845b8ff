"""RNG-provenance static analysis (lint/rnggraph.py, families 22-24 +
the interprocedural prng-key-reuse upgrade) + the DrawLedger runtime
twin.

Fixture halves drive each family on a known-bad snippet and its
known-good variant (parsed, never executed — determinism scope is
entered by giving the fixture a ``fleet/`` path); the package halves
gate the real tree: the rng graph over ``d4pg_tpu/``
must discover streams and branch sites, resolve every declared stream
owner, and carry zero findings, and the ``--rng``/``--all`` CLI
artifacts must exit 0. The runtime half pins DrawLedger semantics
(counting proxy, canonical digest, schedule namespace) and the A/B
equal-seeded-load oracle: two sampler-chaos arms at one seed must
export the same schedule digest.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import d4pg_tpu
from d4pg_tpu.lint import lint_source
from d4pg_tpu.lint.__main__ import main as lint_main
from d4pg_tpu.obs.draw_ledger import LEDGER, SCHEDULE_PREFIX, DrawLedger

pytestmark = pytest.mark.rnglint

PACKAGE_DIR = os.path.dirname(os.path.abspath(d4pg_tpu.__file__))
REPO_ROOT = os.path.dirname(PACKAGE_DIR)


def findings(src, rule, path="fleet/fixture.py"):
    """Fixtures default to a determinism-scoped path — families 22/24
    only patrol fleet/elastic/replay/obs/analysis code."""
    res = lint_source(textwrap.dedent(src), path)
    assert not res.errors, res.errors
    return [f for f in res.findings if f.rule == rule]


# ------------------------------------ R22 rng-ambient-stream --------------

def test_numpy_module_global_draw_fires():
    out = findings("""
        import numpy as np

        def tick():
            return np.random.randn(4)
        """, "rng-ambient-stream")
    assert len(out) == 1
    assert "hidden module-level global stream" in out[0].message


def test_stdlib_random_draw_fires():
    out = findings("""
        import random

        def jitter():
            return random.random() * 0.1
        """, "rng-ambient-stream")
    assert len(out) == 1
    assert "process-global Random" in out[0].message


def test_unseeded_default_rng_fires():
    out = findings("""
        import numpy as np

        def make():
            rng = np.random.default_rng()
            return rng.random()
        """, "rng-ambient-stream")
    assert len(out) == 1
    assert "unseeded" in out[0].message


def test_wallclock_seed_fires():
    out = findings("""
        import time
        import numpy as np

        def make():
            rng = np.random.default_rng(int(time.time()))
            return rng.random()
        """, "rng-ambient-stream")
    assert len(out) == 1
    assert "wall-clock" in out[0].message


def test_branched_component_stream_clean():
    out = findings("""
        import numpy as np

        def make(seed):
            rng = np.random.default_rng(
                np.random.SeedSequence(seed, spawn_key=(1,)))
            return rng.random()
        """, "rng-ambient-stream")
    assert out == []


def test_ambient_outside_determinism_scope_clean():
    """The same ambient draw in a non-scoped module (no fleet/elastic/
    replay/obs/analysis directory, no chaos/traffic/sampler stem) is
    out of the family's jurisdiction."""
    out = findings("""
        import numpy as np

        def tick():
            return np.random.randn(4)
        """, "rng-ambient-stream", path="util/fixture.py")
    assert out == []


# ------------------------------------ R23 rng-stream-thread-escape --------

_SHARED_STREAM = """
    import threading
    import numpy as np

    class Pump:
        def __init__(self, seed):
            self._rng = np.random.default_rng({ctor})

        def start(self):
            threading.Thread(target=self._send).start()
            threading.Thread(target=self._recv).start()

        def _send(self):
            return self._rng.random()

        def _recv(self):
            return self._rng.random()
    """


def test_shared_stream_across_threads_fires():
    out = findings(_SHARED_STREAM.format(ctor="seed"),
                   "rng-stream-thread-escape")
    assert len(out) == 1
    assert "2 distinct thread-spawn targets" in out[0].message
    assert "Pump._send" in out[0].message and "Pump._recv" in out[0].message


def test_branched_stream_across_threads_clean():
    out = findings(
        _SHARED_STREAM.format(
            ctor="np.random.SeedSequence(seed, spawn_key=(7,))"),
        "rng-stream-thread-escape")
    assert out == []


def test_stream_owner_annotation_satisfies():
    """A caller-owned stream may declare its owner; the declaration is
    audited — the named stream must be a discovered seeded component
    stream."""
    src = _SHARED_STREAM.format(ctor="seed") + """
    class Owner:
        def __init__(self, seed):
            self._rng = np.random.default_rng(
                np.random.SeedSequence(seed, spawn_key=(3,)))
    """
    src = src.replace(
        "self._rng = np.random.default_rng(seed)",
        "self._rng = np.random.default_rng(seed)"
        "  # jaxlint: stream-owner=Owner._rng")
    out = [f for f in lint_source(textwrap.dedent(src),
                                  "fleet/fixture.py").findings
           if f.rule == "rng-stream-thread-escape"]
    assert out == []


def test_stream_owner_unresolved_fires():
    src = _SHARED_STREAM.format(ctor="seed").replace(
        "self._rng = np.random.default_rng(seed)",
        "self._rng = np.random.default_rng(seed)"
        "  # jaxlint: stream-owner=Ghost._rng")
    out = [f for f in lint_source(textwrap.dedent(src),
                                  "fleet/fixture.py").findings
           if f.rule == "rng-stream-thread-escape"]
    assert len(out) == 1
    assert "does not resolve" in out[0].message


# ------------------------------------ R24 rng-draw-count-drift ------------

def test_conditional_draw_then_reuse_fires():
    """The PR-12 desync shape: one branch draws, both paths then share
    the stream — the second draw's offset is path-dependent."""
    out = findings("""
        import numpy as np

        def step(flag, seed):
            rng = np.random.default_rng(seed)
            if flag:
                a = rng.random()
            return rng.random()
        """, "rng-draw-count-drift")
    assert len(out) == 1
    assert "path-dependent" in out[0].message


def test_skip_before_rng_use_idiom_clean():
    """Paths that exit the loop body before the FIRST draw are the
    documented skip idiom: every drawing iteration consumes the same
    fixed count, so the event index stays aligned."""
    out = findings("""
        import numpy as np

        def consume(items, seed):
            rng = np.random.default_rng(seed)
            out = []
            for it in items:
                if it is None:
                    continue
                out.append(rng.random())
            return out
        """, "rng-draw-count-drift")
    assert out == []


def test_per_iteration_drift_fires():
    out = findings("""
        import numpy as np

        def consume(items, seed):
            rng = np.random.default_rng(seed)
            out = []
            for it in items:
                u = rng.random()
                if it > 0:
                    u += rng.random()
                out.append(u)
            return out
        """, "rng-draw-count-drift")
    assert len(out) == 1
    assert "per loop iteration" in out[0].message


def test_fixed_draws_per_event_clean():
    """The sanctioned chaos shape: a fixed draw count per event, fate
    decided from the drawn uniforms afterwards."""
    out = findings("""
        import numpy as np

        def consume(items, seed):
            rng = np.random.default_rng(seed)
            out = []
            for it in items:
                u_a, u_b = rng.random(2)
                if u_a < 0.5:
                    out.append(u_b)
            return out
        """, "rng-draw-count-drift")
    assert out == []


def test_persistent_stream_exit_total_drift_fires():
    """An attr stream outlives the frame: two call paths leaving with
    different nonzero totals desync every later consumer."""
    out = findings("""
        import numpy as np

        class Chaos:
            def __init__(self, seed):
                self._rng = np.random.default_rng(seed)

            def step(self, flag):
                u = self._rng.random()
                if flag:
                    u += self._rng.random()
                return u
        """, "rng-draw-count-drift")
    assert len(out) == 1
    assert "path-dependent total" in out[0].message


# ------------------------------------ interprocedural prng-key-reuse ------

def test_key_reuse_across_call_boundary_fires():
    out = findings("""
        import jax

        def helper(key, shape):
            return jax.random.normal(key, shape)

        def run(key):
            x = helper(key, (4,))
            y = jax.random.normal(key, (4,))
            return x + y
        """, "prng-key-reuse", path="fixture.py")
    assert len(out) == 1
    assert "the callee draws from it" in out[0].message


def test_key_split_across_call_boundary_clean():
    out = findings("""
        import jax

        def helper(key, shape):
            return jax.random.normal(key, shape)

        def run(key):
            k1, k2 = jax.random.split(key)
            x = helper(k1, (4,))
            y = jax.random.normal(k2, (4,))
            return x + y
        """, "prng-key-reuse", path="fixture.py")
    assert out == []


# ------------------------------------ package gates -----------------------

@pytest.mark.lint
def test_rng_graph_clean_over_package():
    """Tier-1 gate for the determinism surface: the whole-program rng
    graph over ``d4pg_tpu/`` must discover the component
    streams and their SeedSequence branch sites, resolve every declared
    stream owner, and carry zero findings."""
    from d4pg_tpu.lint.engine import build_rng_graph
    from d4pg_tpu.lint.rnggraph import format_rnggraph

    graph, errors = build_rng_graph([PACKAGE_DIR])
    assert not errors, errors
    assert graph.findings == [], format_rnggraph(graph)
    assert graph.streams, "no RNG streams discovered — walker rot?"
    assert graph.branches, "no SeedSequence branch sites — walker rot?"
    assert graph.scoped > 0
    for spec, status in graph.handlers.items():
        assert status == "ok", (spec, status)
    # the ledger-wrapped chaos/traffic streams must stay discoverable
    # THROUGH the wrap (the lint/runtime twins see the same streams)
    wrapped = [s for s in graph.streams if "+ledger:" in s[3]]
    assert any("schedule." in s[3] for s in wrapped), graph.streams


@pytest.mark.lint
def test_cli_rng_mode_clean():
    """``python -m d4pg_tpu.lint --rng`` is the review artifact for
    determinism PRs; it must exit 0 on the repo and print the stream
    table, the branch sites, and no findings."""
    proc = subprocess.run(
        [sys.executable, "-m", "d4pg_tpu.lint", "--rng", PACKAGE_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "rnggraph:" in proc.stdout
    assert "streams (ctor site -> owner [ctor/seed] draws threads):" \
        in proc.stdout
    assert "branch sites (SeedSequence / spawn):" in proc.stdout
    assert "findings: none" in proc.stdout


def test_rng_cli_mode_fires_on_fixture(tmp_path, capsys):
    """`--rng` exits 1 iff a family fires, 0 on the clean variant. The
    fixture filename carries a scoped stem (chaos) — scope is a path
    property, not a flag."""
    bad = tmp_path / "chaos_bad.py"
    bad.write_text(textwrap.dedent("""
        import numpy as np

        def tick():
            return np.random.randn(4)
        """))
    assert lint_main(["--rng", str(bad)]) == 1
    out = capsys.readouterr().out
    assert "rng-ambient-stream" in out

    good = tmp_path / "chaos_good.py"
    good.write_text(textwrap.dedent("""
        import numpy as np

        def make(seed):
            rng = np.random.default_rng(
                np.random.SeedSequence(seed, spawn_key=(1,)))
            return rng.random()
        """))
    assert lint_main(["--rng", str(good)]) == 0
    out = capsys.readouterr().out
    assert "findings: none" in out
    assert "[default_rng/branched]" in out


def test_json_rng_mode(tmp_path, capsys):
    src = tmp_path / "chaos_mod.py"
    src.write_text(textwrap.dedent("""
        import numpy as np

        def make(seed):
            rng = np.random.default_rng(
                np.random.SeedSequence(seed, spawn_key=(1,)))
            return rng.random()
        """))
    assert lint_main(["--rng", "--json", str(src)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["schema"] == 1 and doc["mode"] == "rng"
    assert doc["findings"] == [] and doc["errors"] == []
    for key in ("functions", "modules", "scoped", "streams", "branches",
                "handlers"):
        assert key in doc, key
    assert len(doc["streams"]) == 1
    row = doc["streams"][0]
    assert set(row) == {"site", "owner", "ctor", "seed", "draws", "threads"}
    assert row["seed"] == "branched"
    assert len(doc["branches"]) == 1


def test_json_all_mode_carries_rng_section(tmp_path, capsys):
    src = tmp_path / "chaos_mod.py"
    src.write_text(textwrap.dedent("""
        import numpy as np

        def make(seed):
            rng = np.random.default_rng(
                np.random.SeedSequence(seed, spawn_key=(1,)))
            return rng.random()
        """))
    assert lint_main(["--all", "--json", str(src)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert "rng" in doc
    assert doc["rng"]["findings"] == [] and doc["rng"]["errors"] == []
    assert doc["rng"]["streams"]


# ------------------------------------ DrawLedger (runtime twin) -----------

def test_draw_ledger_counts_and_reset():
    led = DrawLedger()
    led.count("a")
    led.count("a", 2)
    led.count("b")
    assert led.counts() == {"a": 3, "b": 1}
    led.disarm()
    led.count("a")  # disarmed: no-op
    assert led.counts() == {"a": 3, "b": 1}
    led.reset(armed=True)
    assert led.counts() == {}
    led.count("c")
    assert led.counts() == {"c": 1}


def test_draw_ledger_wrap_is_transparent():
    """The proxy counts draw-method CALLS (the family-24 unit) and
    delegates everything — including the drawn values — unchanged."""
    led = DrawLedger()
    raw = np.random.default_rng(11)
    wrapped = led.wrap("s", np.random.default_rng(11))
    a = wrapped.random(4)
    b = wrapped.integers(0, 10, size=3)
    assert np.array_equal(a, raw.random(4))
    assert np.array_equal(b, raw.integers(0, 10, size=3))
    assert led.counts() == {"s": 2}  # two calls, not seven elements
    # non-draw attributes pass through to the real Generator
    assert wrapped.bit_generator is not None


def test_draw_ledger_digest_is_canonical():
    """Equal counted histories hash equal regardless of arrival order;
    the schedule prefix filters the namespace the A/B drivers pin."""
    one, two = DrawLedger(), DrawLedger()
    one.count("schedule.x")
    one.count("chaos.y", 3)
    two.count("chaos.y", 3)
    two.count("schedule.x")
    assert one.digest() == two.digest()
    assert one.digest(SCHEDULE_PREFIX) == two.digest(SCHEDULE_PREFIX)
    two.count("chaos.y")  # runtime streams differ...
    assert one.digest() != two.digest()
    # ...but the schedule namespace digest is unaffected
    assert one.digest(SCHEDULE_PREFIX) == two.digest(SCHEDULE_PREFIX)
    exp = one.export()
    assert set(exp) == {"streams", "total_draws", "digest",
                        "schedule_digest"}
    assert exp["total_draws"] == 4


def test_component_streams_report_through_global_ledger():
    """TrafficModel construction + the chaos schedules/actor streams
    count into the process ledger when armed, under the documented
    stream names; two identical construction windows export the same
    schedule digest (the equal-seeded-load oracle)."""
    from d4pg_tpu.elastic.traffic import TrafficConfig, TrafficModel
    from d4pg_tpu.fleet.chaos import ChaosConfig, ChaosPolicy

    def window():
        LEDGER.reset(armed=True)
        TrafficModel(TrafficConfig(n_actors=4, seed=3))
        pol = ChaosPolicy(ChaosConfig(
            service_kill_every_s=1.0, service_kill_count=3, seed=3))
        pol.service_kill_schedule(10.0)
        actor = pol.actor_stream(0, "actor-0")
        for _ in range(5):
            actor.next()
        exp = LEDGER.export()
        LEDGER.reset(armed=False)
        return exp

    first, second = window(), window()
    streams = first["streams"]
    assert streams["schedule.traffic.diurnal"] == 1
    assert streams["schedule.traffic.pareto"] == 4  # one per actor lane
    assert streams["schedule.service_kill"] == 3    # one per kill
    assert streams["chaos.actor-0"] == 5            # one call per event
    assert "schedule.traffic.flash" in streams
    assert first["schedule_digest"] == second["schedule_digest"]
    assert first["digest"] == second["digest"]


@pytest.mark.slow
def test_sampler_chaos_arms_pin_schedule_digest():
    """The A/B equal-seeded-load oracle end to end: two sampler-chaos
    arms at one seed — different sample paths, so different runtime
    behaviour — must export the SAME schedule-namespace digest, and
    every run's artifact must carry the draw_ledger block."""
    from d4pg_tpu.fleet.sampler_chaos import (SamplerChaosConfig,
                                              run_sampler_chaos)

    reports = [
        run_sampler_chaos(SamplerChaosConfig(
            sample_path=path, n_actors=2, duration_s=1.5,
            rows_per_sec=30.0, learner_kills=1, seed=9))
        for path in ("dealer", "host")
    ]
    for rep in reports:
        block = rep["draw_ledger"]
        assert set(block) == {"streams", "total_draws", "digest",
                              "schedule_digest"}
        assert block["streams"]["schedule.sampler_kill"] == 1
        assert any(k.startswith("chaos.") for k in block["streams"])
    assert (reports[0]["draw_ledger"]["schedule_digest"]
            == reports[1]["draw_ledger"]["schedule_digest"])
