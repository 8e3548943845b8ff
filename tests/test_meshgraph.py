"""Sharding/collective static analysis (lint/meshgraph.py, families
19-21) + the ReshardSentinel runtime twin.

Fixture halves drive each family on a known-bad snippet and its
known-good variant (parsed, never executed); the package halves gate the
real tree: the mesh graph over ``d4pg_tpu/`` must be clean, every
collective bound, the ``--mesh``/``--all`` CLI artifacts must exit 0,
and the axis/factory mirrors must equal what ``parallel/mesh.py`` and
``parallel/partition.py`` actually declare. The runtime half pins the
fused learner path to ZERO resharding collectives in its compiled HLO.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

import d4pg_tpu
from d4pg_tpu.lint import lint_source
from d4pg_tpu.lint.__main__ import main as lint_main

pytestmark = pytest.mark.meshlint

PACKAGE_DIR = os.path.dirname(os.path.abspath(d4pg_tpu.__file__))
REPO_ROOT = os.path.dirname(PACKAGE_DIR)


def findings(src, rule):
    res = lint_source(textwrap.dedent(src), "fixture.py")
    assert not res.errors, res.errors
    return [f for f in res.findings if f.rule == rule]


# ------------------------------------ R19 collective-axis-unbound ---------

def test_unbound_collective_fires():
    out = findings("""
        import jax

        DATA_AXIS = "data"

        def merge(x):
            return jax.lax.psum(x, DATA_AXIS)
        """, "collective-axis-unbound")
    assert len(out) == 1
    assert "not reachable from any shard_map" in out[0].message


def test_bound_collective_clean():
    out = findings("""
        import jax
        from jax.experimental.shard_map import shard_map

        DATA_AXIS = "data"

        def make(mesh, specs):
            def body(x):
                return jax.lax.psum(x, DATA_AXIS)
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)
        """, "collective-axis-unbound")
    assert out == []


def test_hand_spelled_axis_fires_even_when_bound():
    out = findings("""
        import jax
        from jax.experimental.shard_map import shard_map

        def make(mesh, specs):
            def body(x):
                return jax.lax.psum(x, "data")
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)
        """, "collective-axis-unbound")
    assert len(out) == 1
    assert "hand-spelled" in out[0].message
    assert "DATA_AXIS" in out[0].message


def test_undeclared_axis_fires():
    out = findings("""
        import jax
        from jax.experimental.shard_map import shard_map

        def make(mesh, specs):
            def body(x):
                return jax.lax.pmean(x, "batch")
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)
        """, "collective-axis-unbound")
    assert any("not a declared mesh axis" in f.message for f in out)


def test_axis_bound_by_declaration_satisfies():
    """A helper outside the shard_map lexically may declare its binding
    caller; the declaration is audited — the named frame must itself be
    under a shard_map axis binding."""
    out = findings("""
        import jax
        from jax.experimental.shard_map import shard_map

        DATA_AXIS = "data"

        def make(mesh, specs):
            def body(x):
                return x + 1
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)

        def helper(x):  # jaxlint: axis-bound-by=make.body
            return jax.lax.psum(x, DATA_AXIS)
        """, "collective-axis-unbound")
    assert out == []


def test_axis_bound_by_weak_binder_fires():
    out = findings("""
        import jax

        DATA_AXIS = "data"

        def plain(x):
            return x

        def helper(x):  # jaxlint: axis-bound-by=plain
            return jax.lax.psum(x, DATA_AXIS)
        """, "collective-axis-unbound")
    assert len(out) == 1
    assert "not itself under any shard_map" in out[0].message


def test_axis_bound_by_unresolvable_binder_fires():
    out = findings("""
        import jax

        DATA_AXIS = "data"

        def helper(x):  # jaxlint: axis-bound-by=no_such_frame
            return jax.lax.psum(x, DATA_AXIS)
        """, "collective-axis-unbound")
    assert len(out) == 1
    assert "unauditable" in out[0].message


# ------------------------------------ R20 sharding-spec-drift -------------

def test_spec_drift_fires_through_alias():
    out = findings("""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        def make(mesh):
            spec = NamedSharding(mesh, PartitionSpec("data"))
            return jax.jit(lambda x: x, in_shardings=spec)
        """, "sharding-spec-drift")
    assert len(out) == 1
    assert "raw NamedSharding" in out[0].message


def test_spec_clean_through_factory_helper():
    out = findings("""
        import jax
        from d4pg_tpu.parallel import partition

        def _spec(mesh):
            return partition.batch_sharding(mesh)

        def make(mesh):
            return jax.jit(lambda x: x, out_shardings=_spec(mesh))
        """, "sharding-spec-drift")
    assert out == []


def test_implicit_reshard_fires_on_replacement():
    out = findings("""
        import jax
        from d4pg_tpu.parallel import partition

        def move(x, mesh):
            y = jax.device_put(x, partition.batch_sharding(mesh))
            z = jax.device_put(y, partition.replicated(mesh))
            return z
        """, "sharding-spec-drift")
    assert len(out) == 1
    assert "implicit reshard" in out[0].message


def test_consistent_placement_clean():
    out = findings("""
        import jax
        from d4pg_tpu.parallel import partition

        def move(x, w, mesh):
            y = jax.device_put(x, partition.batch_sharding(mesh))
            z = jax.device_put(w, partition.replicated(mesh))
            return y, z
        """, "sharding-spec-drift")
    assert out == []


# ------------------------------------ R21 donation-alias ------------------

def test_donation_alias_fires_on_duplicate_argument():
    out = findings("""
        import jax

        step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        def run(x):
            return step(x, x)
        """, "donation-alias")
    assert len(out) == 1
    assert "aliases argument" in out[0].message


def test_donation_captured_reference_fires():
    out = findings("""
        import jax

        step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        class Holder:
            def run(self):
                out = step(self._state, self._aux)
                return out
        """, "donation-alias")
    assert len(out) == 1
    assert "live captured reference" in out[0].message


def test_donation_clean_on_rebind_and_copy():
    """Rebinding the donated attribute from the result — the replica
    deep-copy fix shape — and donating a fresh copy are both clean."""
    out = findings("""
        import jax

        step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        class Holder:
            def run(self):
                self._state = step(self._state, self._aux)

        def run_copy(x, aux):
            return step(jax.tree.map(lambda a: a.copy(), x), aux)
        """, "donation-alias")
    assert out == []


def test_donation_clean_on_handoff_to_owner():
    """Donating an owned buffer then swapping the result back through
    the owner (the fused_buffer commit shape) is the sanctioned
    double-buffer pattern."""
    out = findings("""
        import jax

        step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        class Holder:
            def run(self):
                out = step(self._store.arrays, self._aux)
                self._store.swap_arrays(out)
        """, "donation-alias")
    assert out == []


def test_donation_intersection_over_branch_factories():
    """A handle resolving to several jit bindings donates only what EVERY
    binding donates — the second argument of the (0, 1)-donating branch
    must NOT be treated as donated at a shared call site."""
    out = findings("""
        import jax

        def _make(fast):
            if fast:
                return jax.jit(lambda a, b: a + b, donate_argnums=(0, 1))
            return jax.jit(lambda a, b: a + b, donate_argnums=(0,))

        class Holder:
            def run(self):
                self._state = _make(True)(self._state, self._aux)
        """, "donation-alias")
    assert out == []


# ------------------------------------ package gates -----------------------

@pytest.mark.lint
def test_mesh_graph_clean_over_package():
    """Tier-1 gate for the sharding surface: the whole-program mesh graph
    over ``d4pg_tpu/`` must bind every collective, resolve every sharding
    consumer without drift, show every donation rebound or handed back,
    and carry zero findings."""
    from d4pg_tpu.lint.engine import build_mesh_graph
    from d4pg_tpu.lint.meshgraph import format_meshgraph

    graph, errors = build_mesh_graph([PACKAGE_DIR])
    assert not errors, errors
    assert graph.findings == [], format_meshgraph(graph)
    assert graph.shard_maps, "no shard_map sites discovered — walker rot?"
    assert graph.collectives, "no collective uses discovered — walker rot?"
    for site, op, axis, witness, status in graph.collectives:
        assert status == "bound", (site, op, axis, witness, status)
        assert witness.startswith("shard_map:"), (site, witness)
    for site, kind, resolution, status in graph.shardings:
        assert status in ("factory", "tree", "param", "opaque"), (
            site, kind, resolution, status)
    for site, callee, donated, status in graph.donations:
        assert status in ("ok", "handoff"), (site, callee, donated, status)


@pytest.mark.lint
def test_axis_mirror_matches_declared_mesh():
    """The lint package is stdlib-only, so ``meshgraph._DECLARED_AXES``
    mirrors ``parallel/mesh.py`` instead of importing it. This equality
    pin is what makes the mirror safe: any axis added, renamed or
    removed there fails here with the exact constant named."""
    from d4pg_tpu.lint.meshgraph import _DECLARED_AXES
    from d4pg_tpu.parallel import mesh

    declared = {name: value for name, value in vars(mesh).items()
                if name.endswith("_AXIS") and isinstance(value, str)}
    assert _DECLARED_AXES == declared


@pytest.mark.lint
def test_factory_mirror_matches_partition_surface():
    """Every name family 20 accepts as a sanctioned spec source must be
    a real exported callable of ``parallel/partition.py`` — a renamed
    factory would otherwise silently demote clean sites to drift."""
    from d4pg_tpu.lint.meshgraph import _FACTORIES
    from d4pg_tpu.parallel import partition

    assert _FACTORIES <= set(partition.__all__), (
        _FACTORIES - set(partition.__all__))
    for name in _FACTORIES:
        assert callable(getattr(partition, name)), name


@pytest.mark.lint
def test_cli_mesh_mode_clean():
    """``python -m d4pg_tpu.lint --mesh`` is the review artifact for
    sharding PRs; it must exit 0 on the repo, print the axis mirror and
    the binding tables, and report no findings."""
    proc = subprocess.run(
        [sys.executable, "-m", "d4pg_tpu.lint", "--mesh", PACKAGE_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "findings: none" in proc.stdout
    assert "declared axes (parallel/mesh.py mirror):" in proc.stdout
    for const in ("DATA_AXIS", "MODEL_AXIS", "REPLICA_AXIS"):
        assert const in proc.stdout, proc.stdout
    assert "shard_map sites" in proc.stdout
    assert "[bound]" in proc.stdout


def test_mesh_cli_mode_fires_on_fixture(tmp_path, capsys):
    """`--mesh` exits 1 iff a family fires, 0 on the clean variant."""
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        DATA_AXIS = "data"

        def merge(x):
            return jax.lax.psum(x, DATA_AXIS)
        """))
    assert lint_main(["--mesh", str(bad)]) == 1
    out = capsys.readouterr().out
    assert "collectives" in out and "[unbound]" in out

    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent("""
        import jax
        from jax.experimental.shard_map import shard_map

        DATA_AXIS = "data"

        def make(mesh, specs):
            def body(x):
                return jax.lax.psum(x, DATA_AXIS)
            return shard_map(body, mesh=mesh, in_specs=specs,
                             out_specs=specs)
        """))
    assert lint_main(["--mesh", str(good)]) == 0
    out = capsys.readouterr().out
    assert "[bound]" in out and "findings: none" in out


def test_json_mesh_mode(tmp_path, capsys):
    src = tmp_path / "mesh.py"
    src.write_text(textwrap.dedent("""
        import jax

        DATA_AXIS = "data"

        def merge(x):
            return jax.lax.psum(x, DATA_AXIS)
        """))
    rc = lint_main(["--mesh", "--json", str(src)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["schema"] == 1 and doc["mode"] == "mesh"
    assert {"axes", "shard_maps", "collectives", "shardings",
            "donations", "handlers"} <= set(doc)
    assert doc["axes"]["DATA_AXIS"] == "data"
    assert doc["collectives"][0]["status"] == "unbound"
    assert any(f["rule"] == "collective-axis-unbound"
               for f in doc["findings"])


def test_json_all_mode_merges_every_section(tmp_path, capsys):
    """``--all --json`` emits ONE merged document: the syntactic findings
    (which already include every program family) plus all four graph
    artifacts; exit 1 iff anything fires."""
    src = tmp_path / "prog.py"
    src.write_text(textwrap.dedent("""
        import jax

        DATA_AXIS = "data"

        def merge(x):
            return jax.lax.psum(x, DATA_AXIS)
        """))
    rc = lint_main(["--all", "--json", str(src)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["schema"] == 1 and doc["mode"] == "all"
    assert any(f["rule"] == "collective-axis-unbound"
               for f in doc["findings"])
    for section in ("locks", "wire", "fail", "mesh"):
        assert section in doc, sorted(doc)
    # the mesh section re-states its own family's findings
    assert any(f["rule"] == "collective-axis-unbound"
               for f in doc["mesh"]["findings"])
    assert doc["locks"]["cycles"] == []

    src.write_text("x = 1\n")
    assert lint_main(["--all", "--json", str(src)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["findings"] == [] and doc["mesh"]["findings"] == []


# ------------------------------------ ReshardSentinel (runtime twin) ------

def test_reshard_sentinel_counts_reshard_ops_only():
    from d4pg_tpu.io.profiling import ReshardError, ReshardSentinel
    from d4pg_tpu.obs.registry import REGISTRY

    before = REGISTRY.counter("profiling.reshards").value
    hlo = "\n".join([
        "%r0 = all-reduce(%g)",         # expected: gradient reduction
        "%r1 = all-gather(%w)",         # expected: merge broadcast
        "%r2 = all-to-all(%t)",         # reshard: layout move
        "%r3 = collective-permute(%t)",  # reshard: layout move
        "%r4 = all-to-all(%u)",
    ])
    sentinel = ReshardSentinel()
    assert sentinel.inspect_text(hlo) == 3
    assert sentinel.steady_state_reshards == 3
    assert sentinel.ops == {"all-to-all": 2, "collective-permute": 1}
    # published into the unified ledger, same as the other sentinels
    assert REGISTRY.counter("profiling.reshards").value == before + 3
    with pytest.raises(ReshardError, match="all-to-all x2"):
        sentinel.assert_clean("fixture path")


def test_reshard_sentinel_clean_and_publishes_counter():
    import jax
    import jax.numpy as jnp

    from d4pg_tpu.io.profiling import ReshardSentinel
    from d4pg_tpu.obs.registry import REGISTRY

    before = REGISTRY.counter("profiling.reshards").value
    f = jax.jit(lambda x: (x * 2.0).sum())
    sentinel = ReshardSentinel()
    assert sentinel.inspect(f, jnp.ones(16)) == 0
    sentinel.assert_clean()
    assert REGISTRY.counter("profiling.reshards").value == before


def test_fused_learner_path_has_zero_reshards(rng):
    """The headline invariant, pinned in-tree: the fused
    chunk dispatch must compile to zero resharding collectives — the
    runtime proof that no tree crosses layouts mid-program (family 20's
    dynamic twin)."""
    import jax
    import jax.numpy as jnp

    from d4pg_tpu.io.profiling import ReshardSentinel
    from d4pg_tpu.learner import D4PGConfig, init_state
    from d4pg_tpu.learner.fused import make_fused_chunk
    from d4pg_tpu.replay import device_per as dper
    from d4pg_tpu.replay.uniform import TransitionBatch

    cap = 64
    config = D4PGConfig(obs_dim=4, act_dim=2, v_min=-10, v_max=10,
                        n_atoms=11, hidden=(16, 16, 16))
    state = init_state(config, jax.random.key(0))
    storage = TransitionBatch(
        obs=jnp.asarray(rng.standard_normal((cap, 4)), jnp.float32),
        action=jnp.asarray(rng.uniform(-1, 1, (cap, 2)), jnp.float32),
        reward=jnp.asarray(rng.standard_normal(cap), jnp.float32),
        next_obs=jnp.asarray(rng.standard_normal((cap, 4)), jnp.float32),
        done=jnp.zeros(cap, jnp.float32),
        discount=jnp.full(cap, 0.99, jnp.float32),
    )
    trees = dper.insert(dper.init(cap), jnp.arange(cap), 0.6)
    fn = make_fused_chunk(config, k=2, batch_size=8, alpha=0.6,
                          donate=False)
    sentinel = ReshardSentinel()
    sentinel.inspect(fn, state, trees, storage, cap)
    sentinel.assert_clean("fused learner path")
    assert sentinel.steady_state_reshards == 0
