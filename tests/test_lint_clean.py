"""Tier-1 lint gate: the whole d4pg_tpu package must lint clean.

Every hazard jaxlint can see in this codebase is either fixed or carries
an inline ``# jaxlint: disable=<rule>`` suppression whose comment explains
why the pattern is deliberate. A new finding here means a PR introduced a
throughput/correctness hazard (or a rule regression) — fix the code or
justify a suppression, don't weaken the gate.

Marked ``lint`` so the whole-repo AST pass can be deselected with
``-m "not lint"`` when iterating on unrelated tests.
"""

import os
import subprocess
import sys

import pytest

import d4pg_tpu
from d4pg_tpu.lint import lint_paths

PACKAGE_DIR = os.path.dirname(os.path.abspath(d4pg_tpu.__file__))
REPO_ROOT = os.path.dirname(PACKAGE_DIR)


@pytest.mark.lint
def test_package_lints_clean():
    result = lint_paths([PACKAGE_DIR])
    msgs = [f.format() for f in result.findings] + result.errors
    assert result.clean, (
        "jaxlint found unsuppressed hazards:\n" + "\n".join(msgs))


@pytest.mark.lint
def test_bench_and_entrypoints_lint_clean():
    """The entry scripts at the repository root are held to the same bar."""
    files = [os.path.join(REPO_ROOT, n)
             for n in ("chip_smoke.py", "__graft_entry__.py")]
    assert all(os.path.exists(f) for f in files), files
    result = lint_paths(files)
    msgs = [f.format() for f in result.findings] + result.errors
    assert result.clean, (
        "jaxlint found unsuppressed hazards:\n" + "\n".join(msgs))


@pytest.mark.lint
def test_suppression_audit():
    """Audit every ``# jaxlint: disable`` AND ``# jaxlint: guarded-by``
    in the package: a disable must name only REGISTERED rules
    (a typo'd rule id suppresses nothing and rots silently), a
    guarded-by must name a lock the whole-program lock graph actually
    knows (a typo'd lock name vouches for nothing), a ``contained-by``
    must name a handler the exception-flow graph resolved AND verified
    contained-and-counted (status ``ok`` — a typo'd or weak handler
    vouches for nothing), an ``axis-bound-by`` must name a binder the
    sharding graph resolved AND verified bound under a shard_map axis
    (status ``ok`` — same bar), a ``stream-owner`` must name a stream
    the rng graph discovered AND verified seeded or SeedSequence-
    branched (status ``ok`` — same bar), and all must carry a
    justification comment on the flagged line's neighborhood (the
    documented contract — see docs/architecture.md "Suppressions").
    New packages (e.g. fleet/) ride the same audit automatically."""
    import re

    from d4pg_tpu.lint.engine import (
        build_fail_graph, build_lock_graph, build_mesh_graph,
        build_rng_graph,
    )
    from d4pg_tpu.lint.lockgraph import _DEFAULT_TIERS
    from d4pg_tpu.lint.rules import RULES

    directive = re.compile(r"#\s*jaxlint:\s*disable(?:-file)?=([\w,\- ]+)")
    guarded = re.compile(r"#\s*jaxlint:\s*guarded-by=([\w,\- ]+)")
    contained = re.compile(r"#\s*jaxlint:\s*contained-by=([\w\.\-,]+)")
    bound = re.compile(r"#\s*jaxlint:\s*axis-bound-by=([\w\.\-,]+)")
    stream_owner = re.compile(r"#\s*jaxlint:\s*stream-owner=([\w\.\-,]+)")
    graph, _errors = build_lock_graph([PACKAGE_DIR])
    known_locks = set(graph.nodes) | set(_DEFAULT_TIERS)
    fail_graph, _errors = build_fail_graph([PACKAGE_DIR])
    mesh_graph, _errors = build_mesh_graph([PACKAGE_DIR])
    rng_graph, _errors = build_rng_graph([PACKAGE_DIR])
    audited = 0
    problems = []
    files = []
    for dirpath, _dirs, names in os.walk(PACKAGE_DIR):
        files.extend(os.path.join(dirpath, n) for n in names
                     if n.endswith(".py"))
    for path in files:
        with open(path) as f:
            lines = f.readlines()
        for i, line in enumerate(lines):
            m = directive.search(line)
            g = guarded.search(line)
            c = contained.search(line)
            b = bound.search(line)
            s = stream_owner.search(line)
            # the lint package's own docs/fixtures mention the directives
            # in strings — only audit real trailing-comment annotations
            if (m is None and g is None and c is None and b is None
                    and s is None) \
                    or os.sep + "lint" + os.sep in path:
                continue
            audited += 1
            where = f"{os.path.relpath(path, REPO_ROOT)}:{i + 1}"
            if m is not None:
                for rule in m.group(1).replace(" ", "").split(","):
                    if rule not in RULES:
                        problems.append(f"{where}: unknown rule {rule!r}")
            if g is not None:
                for lock in g.group(1).replace(" ", "").split(","):
                    if lock not in known_locks:
                        problems.append(
                            f"{where}: guarded-by names unknown lock "
                            f"{lock!r} (not in the discovered lock graph)")
            if c is not None:
                for spec in c.group(1).split(","):
                    if fail_graph.handlers.get(spec) != "ok":
                        problems.append(
                            f"{where}: contained-by names handler {spec!r} "
                            f"with audit status "
                            f"{fail_graph.handlers.get(spec)!r} (must "
                            f"resolve to a contained-and-counted frame)")
            if b is not None:
                for spec in b.group(1).split(","):
                    if mesh_graph.handlers.get(spec) != "ok":
                        problems.append(
                            f"{where}: axis-bound-by names binder {spec!r} "
                            f"with audit status "
                            f"{mesh_graph.handlers.get(spec)!r} (must "
                            f"resolve to a shard_map-bound frame)")
            if s is not None:
                for spec in s.group(1).split(","):
                    if rng_graph.handlers.get(spec) != "ok":
                        problems.append(
                            f"{where}: stream-owner names stream {spec!r} "
                            f"with audit status "
                            f"{rng_graph.handlers.get(spec)!r} (must "
                            f"resolve to a discovered seeded/branched "
                            f"component stream)")
            lo, hi = max(0, i - 6), min(len(lines), i + 2)
            neighborhood = "".join(lines[lo:hi])
            # justification = at least one comment line near the
            # annotation that is NOT itself a directive
            has_comment = any(
                "#" in nl and not directive.search(nl)
                and not guarded.search(nl) and not contained.search(nl)
                and not bound.search(nl) and not stream_owner.search(nl)
                for nl in lines[lo:hi]) or '"""' in neighborhood
            if not has_comment:
                problems.append(f"{where}: annotation without an adjacent "
                                "justification comment")
    assert audited > 0, "audit found no suppressions — regex rot?"
    assert not problems, "\n".join(problems)


@pytest.mark.lint
def test_lock_graph_clean_over_package():
    """Tier-1 gate for the concurrency plane: the whole-program lock
    graph over ``d4pg_tpu/`` must contain the declared ingest-plane
    locks, carry NO cycles, and only hierarchy-descending tiered edges
    (``test_package_lints_clean`` already fails on ``lock-cycle``/
    ``unguarded-shared-write`` findings; this pins the graph shape the
    ``--locks`` review artifact prints)."""
    from d4pg_tpu.core.locking import HIERARCHY
    from d4pg_tpu.lint.engine import build_lock_graph
    from d4pg_tpu.lint.lockgraph import _DEFAULT_TIERS, format_graph

    graph, errors = build_lock_graph([PACKAGE_DIR])
    assert not errors, errors
    assert graph.cycles == [], format_graph(graph)
    # the ingest plane's locks are all discovered, with their tier
    # labels, and so are the weight plane's three
    for lock, tier in (("_lock", "service"), ("_buffer_lock", "buffer"),
                       ("_commit_cond", "commit"), ("cond", "shard"),
                       ("_ring_locks", "ring"), ("_relay_lock", "wrelay"),
                       ("_frame_lock", "wserve"), ("_store_lock", "wstore"),
                       ("_replica_lock", "replica"), ("_agg_cond", "agg"),
                       ("_pserve_cond", "pserve")):
        assert lock in graph.nodes, sorted(graph.nodes)
        assert graph.nodes[lock] == tier
    # every edge between tier-labeled locks DESCENDS the hierarchy
    tiers = dict(_DEFAULT_TIERS)
    tiers.update({k: v for k, v in graph.nodes.items() if v})
    for (held, acquired) in graph.edges:
        th = HIERARCHY.get(tiers.get(held, ""))
        tb = HIERARCHY.get(tiers.get(acquired, ""))
        if th is not None and tb is not None and held != acquired:
            # name-identity merges unrelated same-named locks (e.g. the
            # sender-side transport._lock with the service lock), so
            # only leaf-held ascents are hard failures — mirroring the
            # lock-cycle rule's leaf-ascent check
            assert not (th <= HIERARCHY["shard"] and tb >= th), (
                f"leaf ascent {held} -> {acquired}: "
                + str(graph.edges[(held, acquired)]))


@pytest.mark.lint
def test_cli_locks_mode_clean():
    """``python -m d4pg_tpu.lint --locks`` is the review artifact for
    concurrency PRs; it must exit 0 (no cycles) on the repo and print
    the graph."""
    proc = subprocess.run(
        [sys.executable, "-m", "d4pg_tpu.lint", "--locks", PACKAGE_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cycles: none" in proc.stdout
    assert "_commit_cond" in proc.stdout


@pytest.mark.lint
def test_cli_module_entrypoint():
    """`python -m d4pg_tpu.lint <package>` is the documented interface; it
    must agree with the library API and exit 0 on the repo."""
    proc = subprocess.run(
        [sys.executable, "-m", "d4pg_tpu.lint", PACKAGE_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.lint
def test_wire_graph_clean_over_package():
    """Tier-1 gate for the protocol surface: the whole-program wire graph
    over ``d4pg_tpu/`` must discover every declared magic with at least
    one pack AND one unpack witness, reproduce the declared flag-bit
    map, and carry zero findings."""
    from d4pg_tpu.lint.engine import build_wire_graph
    from d4pg_tpu.lint.wiregraph import format_registry

    graph, errors = build_wire_graph([PACKAGE_DIR])
    assert not errors, errors
    assert graph.findings == [], format_registry(graph)
    from d4pg_tpu.core import wire

    declared_magics = {spec.magic for spec in wire.REGISTRY.values()}
    assert set(graph.magics) == declared_magics, format_registry(graph)
    for magic, e in graph.magics.items():
        assert e["packs"], f"{magic!r}: no pack witness discovered"
        assert e["unpacks"], f"{magic!r}: no unpack witness discovered"
        assert e["plane"] is not None
    # the discovered flag map IS the declared per-plane allocation
    for plane, bits in wire.PLANE_FLAG_BITS.items():
        if bits:
            assert graph.flags.get(plane) == dict(bits), (plane, graph.flags)
        else:
            assert not graph.flags.get(plane), (plane, graph.flags)


@pytest.mark.lint
def test_wire_mirror_matches_declared_registry():
    """The lint package is stdlib-only, so ``wiregraph._DECLARED``
    mirrors ``core.wire.REGISTRY`` instead of importing it. This pin is
    what makes the mirror safe: any drift — a row added, a format
    changed, a flag reallocated, a crc discipline flipped — fails here
    with the exact rows named."""
    from d4pg_tpu.core import wire
    from d4pg_tpu.lint.wiregraph import _DECLARED

    declared = {
        name: (spec.plane, spec.magic, spec.header, spec.crc,
               tuple(sorted(spec.flags)),
               tuple(fmt for _ext_name, fmt in spec.extensions))
        for name, spec in wire.REGISTRY.items()}
    mirrored = {
        row[0]: (row[1], row[2], row[3], row[4],
                 tuple(sorted(row[5])), tuple(row[6]))
        for row in _DECLARED}
    assert mirrored == declared


@pytest.mark.lint
def test_cli_wire_mode_clean():
    """``python -m d4pg_tpu.lint --wire`` is the review artifact for
    protocol PRs; it must exit 0 on the repo, print every declared
    magic, and report no findings."""
    proc = subprocess.run(
        [sys.executable, "-m", "d4pg_tpu.lint", "--wire", PACKAGE_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "findings: none" in proc.stdout
    for magic in ("0xD4AB", "0xD4E2", "0xD4E3", "0xD4F6", "0xD4F7",
                  "0xD4F8", "0xD4FA", "0xD4FC", "D4RS"):
        assert magic in proc.stdout, proc.stdout
    assert "flag bits:" in proc.stdout


@pytest.mark.lint
@pytest.mark.failflow
def test_fail_graph_clean_over_package():
    """Tier-1 gate for the crash-containment surface: the whole-program
    exception-flow graph over ``d4pg_tpu/`` must show every thread spawn
    contained (or covered by an audited ``contained-by`` declaration),
    every trace begin settled or escrowed, every admission counter
    balanced, and zero findings."""
    from d4pg_tpu.lint.engine import build_fail_graph
    from d4pg_tpu.lint.failgraph import format_failgraph

    graph, errors = build_fail_graph([PACKAGE_DIR])
    assert not errors, errors
    assert graph.findings == [], format_failgraph(graph)
    assert graph.threads, "no thread spawns discovered — walker rot?"
    for site, target, status in graph.threads:
        assert status in ("contained", "no-raise", "contained-by"), (
            site, target, status)
    for site, root, status in graph.spans:
        assert status in ("settled", "escrow"), (site, root, status)
    for site, counter, status in graph.ledger:
        assert status == "balanced", (site, counter, status)
    # the fleet lane spawn's declaration is resolved and verified
    assert graph.handlers.get("ThrottledSender.run") == "ok", graph.handlers
    # the five wire planes' serve/accept loops are all discovered
    discovered = " ".join(t for _s, t, _st in graph.threads)
    for frame in ("TransitionReceiver._accept", "AggregatorServer._serve",
                  "WeightServer._accept", "PolicyInferenceServer._batcher",
                  "ReplayService._commit_loop"):
        assert frame in discovered, discovered


@pytest.mark.lint
@pytest.mark.failflow
def test_cli_fail_mode_clean():
    """``python -m d4pg_tpu.lint --fail`` is the review artifact for
    thread/obs PRs; it must exit 0 on the repo, print the thread-role
    table, and report no findings."""
    proc = subprocess.run(
        [sys.executable, "-m", "d4pg_tpu.lint", "--fail", PACKAGE_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "findings: none" in proc.stdout
    assert "thread roles" in proc.stdout
    assert "contained-by=ThrottledSender.run [ok]" in proc.stdout


@pytest.mark.lint
def test_cli_json_modes_clean():
    """``python -m d4pg_tpu.lint --all --json`` is the single CI
    entrypoint: ONE schema-1 document carrying the syntactic findings
    AND every graph mode's artifact section (the per-mode ``--json``
    documents are encoded by the same helpers, so gating the merged doc
    gates them all). Must exit clean on the repo."""
    import json

    proc = subprocess.run(
        [sys.executable, "-m", "d4pg_tpu.lint", "--all", "--json",
         PACKAGE_DIR],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["schema"] == 1 and doc["mode"] == "all", doc
    assert doc["findings"] == [] and doc["errors"] == [], doc
    assert "suppressed" in doc
    sections = {
        "locks": {"functions", "nodes", "edges", "cycles"},
        "wire": {"functions", "modules", "magics", "flags"},
        "fail": {"functions", "modules", "threads", "spans", "ledger",
                 "handlers"},
        "mesh": {"functions", "modules", "axes", "shard_maps",
                 "collectives", "shardings", "donations", "handlers"},
        "rng": {"functions", "modules", "scoped", "streams", "branches",
                "handlers"},
    }
    for section, keys in sections.items():
        sub = doc[section]
        assert sub["findings"] == [] and sub["errors"] == [], (section, sub)
        assert keys <= set(sub), (section, sorted(sub))
    assert doc["locks"]["cycles"] == []
