"""meshgraph — whole-program sharding & collective static analysis.

Fourth member of the whole-program family (lockgraph: tiers/cycles,
wiregraph: frame-registry symmetry, failgraph: exception flow).  This one
models the *mesh* surface: where ``shard_map`` binds axis names, which
collectives consume them, how sharding specs flow from the partition-rule
core into ``jit``/``device_put`` consumers, and which jitted callables
donate buffers that a caller might still be holding.

Three families over a call-graph-aware index of every jit/``shard_map``/
collective site:

- ``collective-axis-unbound`` (19): every ``psum``/``pmean``/
  ``all_gather``/``axis_index``-style use of an ``axis_name`` must be
  reachable only from a ``shard_map`` (or mesh-context) site binding that
  axis, and the axis identity must be one of the axes declared in
  ``parallel/mesh.py`` — spelled as the declared CONSTANT, never as a raw
  string (a hand-spelled ``'data'`` silently desynchronizes from a mesh
  rename).  Helpers called under a binder established elsewhere may
  declare ``# jaxlint: axis-bound-by=<caller>`` on the def line; the
  declaration is audited like failgraph's ``contained-by`` (the named
  caller must itself resolve to a bound frame).
- ``sharding-spec-drift`` (20): extends family 15 from constructor sites
  to DATAFLOW — an ``in_shardings``/``out_shardings``/``device_put``
  sharding argument must resolve (through local aliases, self-attributes
  and helper returns) to a ``parallel/partition.py`` factory; resolving
  to a raw ``NamedSharding``/``PartitionSpec`` construction reached
  through an alias is flagged, and a tree placed under one rule-resolved
  factory but later re-placed under a different one is an implicit
  reshard.  Device-placement calls (``device_put(x, device)``) resolve to
  a parameter or opaque handle and are deliberately not flagged.
- ``donation-alias`` (21): a call into a ``donate_argnums`` signature
  whose donated argument textually aliases another argument, or is a
  captured reference (``self._x`` / ``obj.attr``) that the call's
  assignment neither rebinds nor hands back to its owner — the PR-10
  replica deep-copy defect shape, caught statically.  Donation
  signatures resolve through module jit bindings, function-local
  ``fn = jax.jit(...)`` aliases, ``self._fn = jax.jit(...)`` /
  ``self._fn = self._make_fn()`` attributes, jit-decorated defs, and
  factory returns (same- and cross-module).

The declared-axis table is MIRRORED from ``parallel/mesh.py`` (and the
factory list from ``parallel/partition.py.__all__``), not imported: the
lint package is stdlib-only by contract.  tests/test_meshgraph.py pins
the mirrors against the real modules.

Pure stdlib (ast) — same contract as the rest of the package.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field

from d4pg_tpu.lint.context import (
    FunctionNode,
    ModuleContext,
    _int_tuple,
    dotted_name,
    last_part,
)
from d4pg_tpu.lint.findings import Finding

MESH_RULES = (
    "collective-axis-unbound",
    "sharding-spec-drift",
    "donation-alias",
)

_AXIS_BOUND_BY = re.compile(r"#\s*jaxlint:\s*axis-bound-by=([\w\.\-,]+)")

# Mirrored, not imported: the lint package is stdlib-only by contract.
# tests/test_meshgraph.py pins this table against parallel/mesh.py —
# any axis added, renamed or removed there fails the pin with the exact
# constant named.
_DECLARED_AXES = {
    "DATA_AXIS": "data",
    "MODEL_AXIS": "model",
    "REPLICA_AXIS": "replica",
}
_AXIS_VALUES = set(_DECLARED_AXES.values())

# Sharding-producing names of parallel/partition.py — the sanctioned
# resolution targets of family 20.  Mirrored (subset of
# partition.__all__; pinned by tests/test_meshgraph.py).
_FACTORIES = {
    "spec", "sharding", "replicated", "one_device", "batch_sharding",
    "stacked_sharding", "replica_sharding", "replicated_spec", "batch_spec",
    "data_spec",
    "stacked_spec", "replica_spec", "shardings_for", "state_specs",
    "state_shardings", "replica_stack_shardings", "match_partition_rules",
}

# Raw sharding constructors — reaching one of these through an alias is
# exactly the drift family 15 cannot see (it only flags the ctor SITE).
_SHARDING_CTORS = {
    "NamedSharding", "PartitionSpec", "PS", "P", "PositionalSharding",
    "GSPMDSharding", "SingleDeviceSharding",
}

# Collective op -> positional index of its axis-name operand (the
# ``axis_name=`` kwarg always wins).  ``fold_in`` is excluded: its second
# operand is DATA (usually an ``axis_index`` value, which is itself a
# family-19 site).
_COLLECTIVES = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1, "psum_scatter": 1,
    "all_gather": 1, "all_to_all": 1, "ppermute": 1, "pbroadcast": 1,
    "axis_index": 0, "axis_size": 0,
}

_JIT_NAMES = {"jit", "pjit"}

_MAX_DEPTH = 6


def _short(path: str) -> str:
    return path.rsplit("/d4pg_tpu/", 1)[-1] if "/d4pg_tpu/" in path else path


def _is_partition_module(path: str) -> bool:
    return path.replace("\\", "/").endswith("parallel/partition.py")


def _unwrap_partial(call: ast.Call) -> ast.expr | None:
    if last_part(dotted_name(call.func)) == "partial" and call.args:
        return call.args[0]
    return None


def _jit_call(node: ast.expr) -> ast.Call | None:
    """The ``jax.jit(...)``/``pjit(...)`` call denoted by ``node`` (through
    one ``partial`` wrapper), else None."""
    if not isinstance(node, ast.Call):
        return None
    inner = _unwrap_partial(node)
    if inner is not None and isinstance(inner, ast.Call):
        return _jit_call(inner)
    if inner is not None:
        return None
    if last_part(dotted_name(node.func)) in _JIT_NAMES:
        return node
    return None


def _decorator_jit_kwargs(node: ast.AST) -> dict[str, ast.expr]:
    """kwargs of a ``@partial(jax.jit, donate_argnums=...)``-style
    decorator on a def (bare ``@jax.jit`` carries none)."""
    out: dict[str, ast.expr] = {}
    for dec in getattr(node, "decorator_list", ()):
        if isinstance(dec, ast.Call):
            target = _unwrap_partial(dec)
            name = last_part(dotted_name(
                target if target is not None else dec.func))
            if name in _JIT_NAMES:
                out.update({k.arg: k.value for k in dec.keywords if k.arg})
    return out


def _bound_lines(source: str) -> dict[int, tuple[str, ...]]:
    out: dict[int, tuple[str, ...]] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _AXIS_BOUND_BY.search(text)
        if m:
            out[i] = tuple(h.strip() for h in m.group(1).split(",")
                           if h.strip())
    return out


# --------------------------------------------------------------------------
# Program index
# --------------------------------------------------------------------------

@dataclass
class _ShardMapSite:
    path: str
    line: int
    col: int
    body_src: str
    axes: frozenset[str]
    bodies: list[ast.AST] = field(default_factory=list)


@dataclass
class _CollectiveSite:
    path: str
    line: int
    col: int
    op: str
    axis_expr: ast.expr | None
    fn_stack: tuple[ast.AST, ...]     # innermost first; () at module scope
    scopes: tuple[ast.AST, ...]       # name-resolution chain, innermost first


@dataclass
class _ShardingSite:
    path: str
    line: int
    col: int
    kind: str                         # in_shardings | out_shardings | ...
    expr: ast.expr
    scopes: tuple[ast.AST, ...]
    cls: str | None


@dataclass
class _CallSite:
    path: str
    call: ast.Call
    stmt: ast.stmt | None
    fn: ast.AST | None                # enclosing function (stmt list owner)
    scopes: tuple[ast.AST, ...]
    cls: str | None


@dataclass
class _Mod:
    ctx: ModuleContext
    # scope node (module tree or function node) -> {name: [value exprs]}
    envs: dict[int, dict[str, list[ast.expr]]]
    # class name -> attr -> [value exprs] (``self.attr = ...`` anywhere)
    self_attrs: dict[str, dict[str, list[ast.expr]]]
    # def node id -> parameter-name set
    params: dict[int, set[str]]
    by_bare: dict[str, list[ast.AST]]
    qual_of: dict[int, str]
    shard_maps: list[_ShardMapSite]
    collectives: list[_CollectiveSite]
    shardings: list[_ShardingSite]
    calls: list[_CallSite]
    bound_ann: dict[int, tuple[str, ...]]   # def lineno -> declared binders


@dataclass
class _Program:
    mods: list[_Mod]
    by_bare: dict[str, list[tuple[_Mod, ast.AST]]]
    by_qual: dict[str, list[tuple[_Mod, ast.AST]]]
    # binding fixpoint: id(def node) -> bound axis set
    bound: dict[int, frozenset[str]] = field(default_factory=dict)


def _mesh_axes(mod: _Mod, scopes: tuple[ast.AST, ...],
               expr: ast.expr | None, depth: int = 0) -> frozenset[str]:
    """Axes a shard_map's ``mesh=`` operand binds.  ``make_mesh`` ->
    (data, model); ``replica_mesh`` -> all three; anything opaque (a
    parameter, ``self.mesh``) conservatively binds every declared axis —
    family 19's teeth are the NO-binder case, not axis-set mismatches on
    handles the AST cannot see."""
    if expr is None or depth > _MAX_DEPTH:
        return frozenset(_AXIS_VALUES)
    if isinstance(expr, ast.Call):
        name = last_part(dotted_name(expr.func))
        if name == "make_mesh":
            return frozenset({"data", "model"})
        if name == "replica_mesh":
            return frozenset(_AXIS_VALUES)
        return frozenset(_AXIS_VALUES)
    if isinstance(expr, ast.Name):
        for val in _lookup(mod, scopes, expr.id):
            return _mesh_axes(mod, scopes, val, depth + 1)
    return frozenset(_AXIS_VALUES)


def _lookup(mod: _Mod, scopes: tuple[ast.AST, ...],
            name: str) -> list[ast.expr]:
    for scope in scopes:
        vals = mod.envs.get(id(scope), {}).get(name)
        if vals:
            return vals
    return []


def _index_module(ctx: ModuleContext) -> _Mod:
    mod = _Mod(ctx=ctx, envs={}, self_attrs={}, params={}, by_bare={},
               qual_of={}, shard_maps=[], collectives=[], shardings=[],
               calls=[], bound_ann=_bound_lines(ctx.source))

    def record_assign(scope: ast.AST, target: ast.expr, value: ast.expr,
                      cls: str | None) -> None:
        if isinstance(target, ast.Name):
            mod.envs.setdefault(id(scope), {}).setdefault(
                target.id, []).append(value)
        elif (isinstance(target, ast.Attribute)
              and isinstance(target.value, ast.Name)
              and target.value.id == "self" and cls):
            mod.self_attrs.setdefault(cls, {}).setdefault(
                target.attr, []).append(value)

    def visit(node: ast.AST, scopes: tuple[ast.AST, ...],
              fn_stack: tuple[ast.AST, ...], cls: str | None,
              stmt: ast.stmt | None, qual: str) -> None:
        for child in ast.iter_child_nodes(node):
            c_scopes, c_stack, c_cls, c_stmt, c_qual = (
                scopes, fn_stack, cls, stmt, qual)
            if isinstance(child, ast.stmt):
                c_stmt = child
            if isinstance(child, ast.ClassDef):
                c_cls = child.name
                c_qual = f"{qual}{child.name}."
            elif isinstance(child, FunctionNode):
                c_scopes = (child, *scopes)
                c_stack = (child, *fn_stack)
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    name = child.name
                    mod.by_bare.setdefault(name, []).append(child)
                    mod.qual_of[id(child)] = f"{qual}{name}"
                    c_qual = f"{qual}{name}."
                args = child.args
                mod.params[id(child)] = {
                    a.arg for a in (args.posonlyargs + args.args
                                    + args.kwonlyargs)}
                if args.vararg:
                    mod.params[id(child)].add(args.vararg.arg)
                if args.kwarg:
                    mod.params[id(child)].add(args.kwarg.arg)
            elif isinstance(child, ast.Assign):
                for t in child.targets:
                    targets = t.elts if isinstance(
                        t, (ast.Tuple, ast.List)) else [t]
                    for one in targets:
                        record_assign(scopes[0], one, child.value, cls)
            elif isinstance(child, ast.AnnAssign) and child.value is not None:
                record_assign(scopes[0], child.target, child.value, cls)
            elif isinstance(child, ast.Call):
                _index_call(mod, child, scopes, fn_stack, cls, stmt)
            visit(child, c_scopes, c_stack, c_cls, c_stmt, c_qual)

    visit(ctx.tree, (ctx.tree,), (), None, None, "")
    return mod


def _index_call(mod: _Mod, call: ast.Call, scopes: tuple[ast.AST, ...],
                fn_stack: tuple[ast.AST, ...], cls: str | None,
                stmt: ast.stmt | None) -> None:
    path = mod.ctx.path
    name = last_part(dotted_name(call.func))
    kwargs = {k.arg: k.value for k in call.keywords if k.arg}

    if name == "shard_map":
        bodies: list[ast.AST] = []
        body_expr = call.args[0] if call.args else kwargs.get("f")
        if body_expr is not None:
            bodies.extend(_body_fns(mod, scopes, body_expr))
        site = _ShardMapSite(
            path=path, line=call.lineno, col=call.col_offset,
            body_src=ast.unparse(body_expr) if body_expr is not None
            else "?",
            axes=_mesh_axes(mod, scopes, kwargs.get("mesh")),
            bodies=bodies)
        mod.shard_maps.append(site)

    if name in _COLLECTIVES:
        pos = _COLLECTIVES[name]
        axis_expr = kwargs.get("axis_name")
        if axis_expr is None and len(call.args) > pos:
            axis_expr = call.args[pos]
        mod.collectives.append(_CollectiveSite(
            path=path, line=call.lineno, col=call.col_offset, op=name,
            axis_expr=axis_expr, fn_stack=fn_stack, scopes=scopes))

    jit = _jit_call(call)
    if jit is not None:
        jkw = {k.arg: k.value for k in jit.keywords if k.arg}
        for kind in ("in_shardings", "out_shardings"):
            if kind in jkw:
                mod.shardings.append(_ShardingSite(
                    path=path, line=call.lineno, col=call.col_offset,
                    kind=kind, expr=jkw[kind], scopes=scopes, cls=cls))
    if name == "device_put":
        spec = call.args[1] if len(call.args) > 1 else kwargs.get("device")
        if spec is not None:
            mod.shardings.append(_ShardingSite(
                path=path, line=call.lineno, col=call.col_offset,
                kind="device_put", expr=spec, scopes=scopes, cls=cls))
    if name == "make_array_from_process_local_data":
        spec = call.args[0] if call.args else kwargs.get("sharding")
        if spec is not None:
            mod.shardings.append(_ShardingSite(
                path=path, line=call.lineno, col=call.col_offset,
                kind="process_local", expr=spec, scopes=scopes, cls=cls))

    if isinstance(call.func, (ast.Name, ast.Attribute, ast.Call)):
        mod.calls.append(_CallSite(
            path=path, call=call, stmt=stmt,
            fn=fn_stack[0] if fn_stack else None, scopes=scopes, cls=cls))


def _body_fns(mod: _Mod, scopes: tuple[ast.AST, ...],
              expr: ast.expr) -> list[ast.AST]:
    """Function nodes a shard_map body expression can denote: a bare name
    (every same-module def so named — mark-all keeps the pass biased
    toward bound), a lambda (plus the defs its body references), or a
    ``partial(f, ...)`` wrapper."""
    if isinstance(expr, ast.Call):
        inner = _unwrap_partial(expr)
        return _body_fns(mod, scopes, inner) if inner is not None else []
    if isinstance(expr, ast.Lambda):
        out: list[ast.AST] = [expr]
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                out.extend(mod.by_bare.get(node.id, ()))
        return out
    if isinstance(expr, ast.Name):
        return list(mod.by_bare.get(expr.id, ()))
    return []


def build_program(ctxs: list[ModuleContext]) -> _Program:
    mods = [_index_module(ctx) for ctx in ctxs]
    by_bare: dict[str, list[tuple[_Mod, ast.AST]]] = {}
    by_qual: dict[str, list[tuple[_Mod, ast.AST]]] = {}
    for mod in mods:
        for name, nodes in mod.by_bare.items():
            for node in nodes:
                by_bare.setdefault(name, []).append((mod, node))
        for name, nodes in mod.by_bare.items():
            for node in nodes:
                qual = mod.qual_of.get(id(node), name)
                by_qual.setdefault(qual, []).append((mod, node))
    prog = _Program(mods=mods, by_bare=by_bare, by_qual=by_qual)
    _propagate_bindings(prog)
    return prog


def _propagate_bindings(prog: _Program) -> None:
    """Fixpoint: a function passed to shard_map is bound with that site's
    axes; everything lexically nested in OR referenced by bare name from
    a bound function inherits the axes (mark-all-candidates across
    modules — conservative toward bound, family 19 only fires when no
    binder is reachable at all)."""
    work: list[tuple[ast.AST, frozenset[str]]] = []
    for mod in prog.mods:
        for site in mod.shard_maps:
            for body in site.bodies:
                work.append((body, site.axes))

    mod_of: dict[int, _Mod] = {}
    for mod in prog.mods:
        for nodes in mod.by_bare.values():
            for node in nodes:
                mod_of[id(node)] = mod
        for sm in mod.shard_maps:
            for body in sm.bodies:
                mod_of.setdefault(id(body), mod)

    while work:
        node, axes = work.pop()
        have = prog.bound.get(id(node), frozenset())
        if axes <= have:
            continue
        axes = axes | have
        prog.bound[id(node)] = axes
        mod = mod_of.get(id(node))
        for child in ast.walk(node):
            if isinstance(child, FunctionNode) and child is not node:
                mod_of.setdefault(id(child), mod)
                work.append((child, axes))
            if (isinstance(child, ast.Name)
                    and isinstance(child.ctx, ast.Load)):
                if mod is not None:
                    for cand in mod.by_bare.get(child.id, ()):
                        work.append((cand, axes))
                else:
                    for cmod, cand in prog.by_bare.get(child.id, ()):
                        work.append((cand, axes))


# --------------------------------------------------------------------------
# Family 19 — collective-axis-unbound
# --------------------------------------------------------------------------

def _resolve_axis(mod: _Mod, site: _CollectiveSite,
                  expr: ast.expr | None, depth: int = 0
                  ) -> tuple[str, str]:
    """(axis value or '?', status): 'pinned' (declared constant),
    'literal' (hand-spelled string equal to a declared axis), 'unknown'
    (string naming no declared axis), 'opaque' (parameter / handle)."""
    if expr is None or depth > _MAX_DEPTH:
        return "?", "opaque"
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        if expr.value in _AXIS_VALUES:
            return expr.value, "literal"
        return expr.value, "unknown"
    name = last_part(dotted_name(expr))
    if name in _DECLARED_AXES:
        return _DECLARED_AXES[name], "pinned"
    if isinstance(expr, ast.Name):
        for val in _lookup(mod, site.scopes, expr.id):
            return _resolve_axis(mod, site, val, depth + 1)
    if isinstance(expr, (ast.Tuple, ast.List)) and expr.elts:
        # multi-axis collective: report the first non-opaque element
        for elt in expr.elts:
            axis, status = _resolve_axis(mod, site, elt, depth + 1)
            if status != "opaque":
                return axis, status
        return "?", "opaque"
    return "?", "opaque"


def _resolve_binder(prog: _Program, spec: str) -> list[tuple[_Mod, ast.AST]]:
    cands = prog.by_qual.get(spec, [])
    if not cands:
        cands = prog.by_bare.get(spec.rsplit(".", 1)[-1], [])
    return cands


def _check_collectives(prog: _Program, graph: "MeshGraph", emit) -> None:
    for mod in prog.mods:
        for site in mod.collectives:
            where = f"{_short(site.path)}:{site.line}"
            axis, axis_status = _resolve_axis(mod, site, site.axis_expr)

            if axis_status == "literal":
                emit("collective-axis-unbound", site.path, site.line,
                     site.col,
                     f"{site.op} axis {axis!r} is hand-spelled — use the "
                     f"declared constant from parallel/mesh.py "
                     f"({_axis_const(axis)}) so a mesh rename cannot "
                     f"silently desynchronize the collective")
            elif axis_status == "unknown":
                emit("collective-axis-unbound", site.path, site.line,
                     site.col,
                     f"{site.op} names axis {axis!r}, which is not a "
                     f"declared mesh axis (parallel/mesh.py declares "
                     f"{sorted(_AXIS_VALUES)})")

            binder = None
            for fn in site.fn_stack:
                axes = prog.bound.get(id(fn))
                if axes is None:
                    continue
                if axis_status == "opaque" or axis in axes:
                    binder = fn
                    break
            if binder is not None:
                qual = mod.qual_of.get(id(binder), "<lambda>")
                graph.collectives.append(
                    (where, site.op, axis, f"shard_map:{qual}", "bound"))
                continue

            # no reachable binder: an audited axis-bound-by declaration
            # on the innermost enclosing def is the only way out
            declared = ()
            for fn in site.fn_stack:
                declared = mod.bound_ann.get(fn.lineno, ())
                if declared:
                    break
            if declared:
                status = "declared"
                for spec in declared:
                    cands = _resolve_binder(prog, spec)
                    if not cands:
                        graph.handlers[spec] = "unresolved"
                        status = "declared!"
                        emit("collective-axis-unbound", site.path,
                             site.line, site.col,
                             f"axis-bound-by={spec}: declared binder does "
                             f"not resolve to a known function — the "
                             f"binding declaration is unauditable")
                    elif not any(id(n) in prog.bound for _m, n in cands):
                        graph.handlers[spec] = "weak"
                        status = "declared!"
                        emit("collective-axis-unbound", site.path,
                             site.line, site.col,
                             f"axis-bound-by={spec}: declared binder is "
                             f"not itself under any shard_map axis "
                             f"binding — same bar as a direct binding")
                    else:
                        graph.handlers.setdefault(spec, "ok")
                graph.collectives.append(
                    (where, site.op, axis,
                     "axis-bound-by=" + ",".join(declared), status))
                continue

            graph.collectives.append((where, site.op, axis, "-", "unbound"))
            emit("collective-axis-unbound", site.path, site.line, site.col,
                 f"{site.op}({axis!r}) is not reachable from any shard_map "
                 f"site binding that axis — outside a binder the collective "
                 f"is an unbound-axis trace error at best and a silent "
                 f"cross-replica leak at worst; move it under the binding "
                 f"shard_map or declare `# jaxlint: axis-bound-by=<caller>`")


def _axis_const(value: str) -> str:
    for const, v in _DECLARED_AXES.items():
        if v == value:
            return const
    return "?"


# --------------------------------------------------------------------------
# Family 20 — sharding-spec-drift
# --------------------------------------------------------------------------

def _resolve_spec(prog: _Program, mod: _Mod, site: _ShardingSite,
                  expr: ast.expr, depth: int = 0) -> tuple[str, str]:
    """(status, label).  status: 'factory' (partition.py), 'ctor' (raw
    sharding constructor reached through dataflow — the drift), 'param',
    'opaque', 'tree' (composite whose elements all resolved clean)."""
    if depth > _MAX_DEPTH:
        return "opaque", "..."
    if isinstance(expr, ast.Constant):
        return "opaque", repr(expr.value)
    if isinstance(expr, ast.Call):
        name = last_part(dotted_name(expr.func))
        if name in _FACTORIES:
            return "factory", name
        if name in _SHARDING_CTORS:
            return "ctor", name
        # helper call: resolve through its returns (same/cross module)
        for cand_mod, cand in _call_defs(prog, mod, site, expr):
            for ret in _return_exprs(cand):
                st, label = _resolve_spec(prog, cand_mod,
                                          _site_in(cand_mod, cand, site),
                                          ret, depth + 1)
                if st in ("factory", "ctor"):
                    return st, f"{name}->{label}"
        return "opaque", name or ast.unparse(expr)[:40]
    if isinstance(expr, (ast.Tuple, ast.List, ast.Dict)):
        elts = (list(expr.values) if isinstance(expr, ast.Dict)
                else list(expr.elts))
        labels = []
        for elt in elts:
            if elt is None:
                continue
            st, label = _resolve_spec(prog, mod, site, elt, depth + 1)
            if st == "ctor":
                return "ctor", label
            labels.append(label)
        return "tree", "(" + ", ".join(dict.fromkeys(labels)) + ")"
    if isinstance(expr, ast.Name):
        for scope in site.scopes:
            if expr.id in mod.params.get(id(scope), ()):  # parameter
                return "param", expr.id
            vals = mod.envs.get(id(scope), {}).get(expr.id)
            if vals:
                for val in vals:
                    st, label = _resolve_spec(prog, mod, site, val,
                                              depth + 1)
                    if st != "opaque":
                        return st, label
                return "opaque", expr.id
        return "opaque", expr.id
    if isinstance(expr, ast.Attribute):
        if (isinstance(expr.value, ast.Name) and expr.value.id == "self"
                and site.cls):
            vals = mod.self_attrs.get(site.cls, {}).get(expr.attr, ())
            for val in vals:
                st, label = _resolve_spec(prog, mod, site, val, depth + 1)
                if st != "opaque":
                    return st, label
        return "opaque", ast.unparse(expr)
    if isinstance(expr, ast.IfExp):
        for branch in (expr.body, expr.orelse):
            st, label = _resolve_spec(prog, mod, site, branch, depth + 1)
            if st != "opaque":
                return st, label
        return "opaque", ast.unparse(expr)[:40]
    return "opaque", ast.unparse(expr)[:40]


def _site_in(mod: _Mod, fn: ast.AST, site: _ShardingSite) -> _ShardingSite:
    """A resolution context rooted at ``fn`` (for helper-return chasing)."""
    return _ShardingSite(path=mod.ctx.path, line=site.line, col=site.col,
                         kind=site.kind, expr=site.expr,
                         scopes=(fn, mod.ctx.tree), cls=site.cls)


def _call_defs(prog: _Program, mod: _Mod, site, expr: ast.Call
               ) -> list[tuple[_Mod, ast.AST]]:
    """Defs a helper call can reach: same-class ``self._m()`` methods,
    then bare-name candidates (same module first, then program-wide)."""
    func = expr.func
    if (isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name)
            and func.value.id == "self"):
        name = func.attr
    else:
        name = last_part(dotted_name(func))
    if not name:
        return []
    local = [(mod, n) for n in mod.by_bare.get(name, ())]
    if local:
        return local
    return list(prog.by_bare.get(name, ()))[:4]


def _return_exprs(fn: ast.AST) -> list[ast.expr]:
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            out.append(node.value)
    if isinstance(fn, ast.Lambda):
        out.append(fn.body)
    return out


def _check_shardings(prog: _Program, graph: "MeshGraph", emit) -> None:
    for mod in prog.mods:
        if _is_partition_module(mod.ctx.path):
            # the factory core itself constructs PS/NamedSharding — the
            # same exemption family 15 grants it
            continue
        for site in mod.shardings:
            status, label = _resolve_spec(prog, mod, site, site.expr)
            where = f"{_short(site.path)}:{site.line}"
            graph.shardings.append((where, site.kind, label, status))
            if status == "ctor":
                emit("sharding-spec-drift", site.path, site.line, site.col,
                     f"{site.kind} resolves to raw {label} construction "
                     f"outside parallel/partition.py — sharding specs flow "
                     f"from the partition-rule factories so layout "
                     f"decisions stay in one audited table")
        _check_reshard_flow(prog, mod, graph, emit)


def _check_reshard_flow(prog: _Program, mod: _Mod, graph: "MeshGraph",
                        emit) -> None:
    """Implicit reshard: within one function, a value placed under one
    rule-resolved factory and later re-placed under a DIFFERENT one —
    the device round of copies family 20's runtime twin
    (``ReshardSentinel``) counts in compiled HLO."""
    for fn_id, env in list(mod.envs.items()):
        producers: dict[str, tuple[str, int]] = {}
        sites = []
        for name, vals in env.items():
            for val in vals:
                if not (isinstance(val, ast.Call)
                        and last_part(dotted_name(val.func)) == "device_put"
                        and len(val.args) > 1):
                    continue
                fake = _ShardingSite(path=mod.ctx.path, line=val.lineno,
                                     col=val.col_offset, kind="device_put",
                                     expr=val.args[1],
                                     scopes=_scopes_for(mod, fn_id),
                                     cls=_cls_for(mod, fn_id))
                st, label = _resolve_spec(prog, mod, fake, val.args[1])
                if st != "factory":
                    continue
                src = val.args[0]
                sites.append((name, label, val))
                if isinstance(src, ast.Name) and src.id in producers:
                    prev_label, prev_line = producers[src.id]
                    if prev_label != label:
                        emit("sharding-spec-drift", mod.ctx.path,
                             val.lineno, val.col_offset,
                             f"tree {src.id!r} placed under "
                             f"partition.{prev_label} (line {prev_line}) "
                             f"is re-placed under partition.{label} — an "
                             f"implicit reshard (a full device-to-device "
                             f"copy); place it once under the spec its "
                             f"consumer needs")
                producers[name] = (label, val.lineno)


def _scopes_for(mod: _Mod, scope_id: int) -> tuple[ast.AST, ...]:
    for nodes in mod.by_bare.values():
        for node in nodes:
            if id(node) == scope_id:
                return (node, mod.ctx.tree)
    return (mod.ctx.tree,)


def _cls_for(mod: _Mod, scope_id: int) -> str | None:
    qual = None
    for nodes in mod.by_bare.values():
        for node in nodes:
            if id(node) == scope_id:
                qual = mod.qual_of.get(id(node))
    if qual and "." in qual:
        head = qual.split(".", 1)[0]
        if head in mod.self_attrs or head[:1].isupper():
            return head
    return None


# --------------------------------------------------------------------------
# Family 21 — donation-alias
# --------------------------------------------------------------------------

def _intersect(sets: list[set[int]]) -> tuple[int, ...]:
    """Must-donate set: a handle resolving to several jit bindings (the
    two branches of a factory) is treated as donating only the argnums
    EVERY binding donates — family 21 flags certainly-donated arguments,
    never maybe-donated ones."""
    live = [s for s in sets if s]
    if not live:
        return ()
    out = set(live[0])
    for s in live[1:]:
        out &= s
    return tuple(sorted(out))


def _donate_of_expr(prog: _Program, mod: _Mod, scopes, cls,
                    expr: ast.expr, depth: int = 0) -> tuple[int, ...]:
    """donate_argnums a callable-valued expression certainly resolves to
    (intersection over branches/returns); () when none or
    unresolvable."""
    if depth > _MAX_DEPTH:
        return ()
    jit = _jit_call(expr) if isinstance(expr, ast.Call) else None
    if jit is not None:
        kw = {k.arg: k.value for k in jit.keywords if k.arg}
        return _int_tuple(kw.get("donate_argnums"))
    if isinstance(expr, ast.Call):
        sets = [set(_donate_of_fn_returns(prog, cand_mod, cand, depth + 1))
                for cand_mod, cand in _call_defs(prog, mod, None, expr)]
        return _intersect(sets)
    if isinstance(expr, ast.Name):
        for scope in scopes:
            vals = mod.envs.get(id(scope), {}).get(expr.id)
            if vals:
                return _intersect([
                    set(_donate_of_expr(prog, mod, scopes, cls, val,
                                        depth + 1))
                    for val in vals])
        binding = mod.ctx.jit_bindings.get(expr.id)
        if binding is not None and binding.donate_argnums:
            return binding.donate_argnums
        return _intersect([
            set(_int_tuple(
                _decorator_jit_kwargs(node).get("donate_argnums")))
            for node in mod.by_bare.get(expr.id, ())])
    if isinstance(expr, ast.Attribute):
        if (isinstance(expr.value, ast.Name) and expr.value.id == "self"
                and cls):
            return _intersect([
                set(_donate_of_expr(prog, mod, scopes, cls, val,
                                    depth + 1))
                for val in mod.self_attrs.get(cls, {}).get(expr.attr, ())])
    return ()


def _donate_of_fn_returns(prog: _Program, mod: _Mod, fn: ast.AST,
                          depth: int) -> tuple[int, ...]:
    sets: list[set[int]] = []
    scopes = (fn, mod.ctx.tree)
    cls = _cls_for(mod, id(fn))
    for ret in _return_exprs(fn):
        got = set(_donate_of_expr(prog, mod, scopes, cls, ret, depth))
        # ``return name`` where name is a jit-decorated nested def
        if isinstance(ret, ast.Name):
            for node in ast.walk(fn):
                if (isinstance(node, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))
                        and node.name == ret.id):
                    got |= set(_int_tuple(_decorator_jit_kwargs(node)
                                          .get("donate_argnums")))
        sets.append(got)
    return _intersect(sets)


def _stmt_targets(stmt: ast.stmt | None) -> list[str]:
    if not isinstance(stmt, ast.Assign):
        return []
    out = []
    for t in stmt.targets:
        elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
        out.extend(ast.unparse(e) for e in elts)
    return out


def _handed_back(fn: ast.AST | None, stmt: ast.stmt | None,
                 base_src: str, bound_names: list[str]) -> bool:
    """True when a statement after ``stmt`` passes one of the call's
    result names back into the donated reference's owner — the
    ``self._store.swap_arrays(storage)`` shape — or rebinds the donated
    expression directly."""
    if fn is None or stmt is None:
        return False
    after = [n for n in ast.walk(fn)
             if isinstance(n, ast.stmt) and n.lineno > stmt.lineno]
    for n in after:
        for targ in _stmt_targets(n):
            if targ == base_src or targ.startswith(base_src + "."):
                return True
        for call in ast.walk(n):
            if not isinstance(call, ast.Call):
                continue
            func = call.func
            if not isinstance(func, ast.Attribute):
                continue
            recv = ast.unparse(func.value)
            if recv != base_src and not base_src.startswith(recv + "."):
                continue
            for arg in call.args:
                if (isinstance(arg, ast.Name)
                        and arg.id in bound_names):
                    return True
    return False


def _check_donations(prog: _Program, graph: "MeshGraph", emit) -> None:
    for mod in prog.mods:
        for cs in mod.calls:
            donated = _donate_of_expr(prog, mod, cs.scopes, cs.cls,
                                      cs.call.func)
            if not donated:
                continue
            where = f"{_short(cs.path)}:{cs.call.lineno}"
            target = ast.unparse(cs.call.func)
            targets = _stmt_targets(cs.stmt)
            bound_names = [t for t in targets if "." not in t
                           and "[" not in t]
            status = "ok"
            args = cs.call.args
            for idx in donated:
                if idx >= len(args):
                    continue
                arg = args[idx]
                arg_src = ast.unparse(arg)
                for j, other in enumerate(args):
                    if j != idx and ast.unparse(other) == arg_src:
                        status = "alias"
                        emit("donation-alias", cs.path, cs.call.lineno,
                             cs.call.col_offset,
                             f"{target}: donated argument {idx} "
                             f"({arg_src}) aliases argument {j} — XLA "
                             f"frees the buffer while the aliased operand "
                             f"still reads it; pass an independent copy "
                             f"(the replica deep-copy defect shape)")
                        break
                if status == "alias":
                    continue
                if isinstance(arg, (ast.Attribute, ast.Subscript)):
                    if arg_src in targets:
                        continue   # rebound by the same statement
                    base = ast.unparse(arg.value)
                    if _handed_back(cs.fn, cs.stmt, base, bound_names):
                        status = "handoff" if status == "ok" else status
                        continue
                    status = "captured"
                    emit("donation-alias", cs.path, cs.call.lineno,
                         cs.call.col_offset,
                         f"{target}: donated argument {idx} ({arg_src}) "
                         f"is a live captured reference the call neither "
                         f"rebinds nor hands back to its owner — after "
                         f"donation the holder points at freed memory; "
                         f"rebind the attribute from the result (or swap "
                         f"it back through the owning object)")
            graph.donations.append(
                (where, target, ",".join(map(str, donated)), status))


# --------------------------------------------------------------------------
# Graph artifact + analyze
# --------------------------------------------------------------------------

@dataclass
class MeshGraph:
    functions: int = 0
    modules: int = 0
    # declared axis mirror (constant name -> axis string)
    axes: dict[str, str] = field(default_factory=dict)
    # shard_map rows: (site, body src, bound-axes csv)
    shard_maps: list[tuple[str, str, str]] = field(default_factory=list)
    # collective rows: (site, op, axis, binding witness, status)
    collectives: list[tuple[str, str, str, str, str]] = field(
        default_factory=list)
    # sharding dataflow rows: (site, kind, resolution, status)
    shardings: list[tuple[str, str, str, str]] = field(default_factory=list)
    # donation rows: (site, callee, donated argnums csv, status)
    donations: list[tuple[str, str, str, str]] = field(default_factory=list)
    # axis-bound-by audit surface: spec -> ok | unresolved | weak
    handlers: dict[str, str] = field(default_factory=dict)
    findings: list[Finding] = field(default_factory=list)


def analyze(ctxs: list[ModuleContext],
            rules: list[str] | None = None) -> MeshGraph:
    prog = build_program(ctxs)
    n_fns = sum(len(nodes) for mod in prog.mods
                for nodes in mod.by_bare.values())
    graph = MeshGraph(functions=n_fns, modules=len(prog.mods),
                      axes=dict(_DECLARED_AXES))
    active = set(rules if rules is not None else MESH_RULES)

    def emit(rule: str, path: str, line: int, col: int, msg: str) -> None:
        if rule in active:
            graph.findings.append(Finding(path, line, col, rule, msg))

    for mod in prog.mods:
        for site in mod.shard_maps:
            graph.shard_maps.append(
                (f"{_short(site.path)}:{site.line}", site.body_src,
                 ",".join(sorted(site.axes))))

    _check_collectives(prog, graph, emit)
    _check_shardings(prog, graph, emit)
    _check_donations(prog, graph, emit)
    return graph


def format_meshgraph(graph: MeshGraph) -> str:
    lines = [
        f"meshgraph: {graph.modules} modules, {graph.functions} functions, "
        f"{len(graph.shard_maps)} shard_map sites, "
        f"{len(graph.collectives)} collective uses, "
        f"{len(graph.shardings)} sharding consumers, "
        f"{len(graph.donations)} donation calls",
        "",
        "declared axes (parallel/mesh.py mirror):",
    ]
    for const, value in graph.axes.items():
        lines.append(f"  {const} = {value!r}")
    lines.append("")
    lines.append("shard_map sites (site -> body [bound axes]):")
    for site, body, axes in sorted(graph.shard_maps):
        lines.append(f"  {site} -> {body} [{axes}]")
    lines.append("")
    lines.append("collectives (site, op(axis), binding witness, status):")
    for site, op, axis, witness, status in sorted(graph.collectives):
        lines.append(f"  {site} {op}({axis}) <- {witness} [{status}]")
    lines.append("")
    lines.append("sharding dataflow (site, kind, resolution, status):")
    for site, kind, label, status in sorted(graph.shardings):
        lines.append(f"  {site} {kind} = {label} [{status}]")
    lines.append("")
    lines.append("donation sites (site, callee, donated, status):")
    for site, callee, donated, status in sorted(graph.donations):
        lines.append(f"  {site} {callee}({donated}) [{status}]")
    if graph.handlers:
        lines.append("")
        lines.append("declared axis binders:")
        for spec, status in sorted(graph.handlers.items()):
            lines.append(f"  axis-bound-by={spec} [{status}]")
    lines.append("")
    if graph.findings:
        lines.append(f"{len(graph.findings)} finding(s):")
        for f in graph.findings:
            lines.append(f"  {f.format()}")
    else:
        lines.append("findings: none")
    return "\n".join(lines)
