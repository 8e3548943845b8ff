"""The jaxlint rule catalog.

Twenty-four rule families, each targeting a hazard that silently costs
throughput or correctness on this stack (see docs/architecture.md "Static
analysis & perf sentinels" for the rationale and suppression policy):

- ``prng-key-reuse``       — same key consumed by two samplers
- ``host-sync-in-jit``     — host/device sync points under a trace
- ``recompile-hazard``     — patterns that defeat the jit cache
- ``use-after-donation``   — reading a buffer after ``donate_argnums`` took it
- ``tracer-leak``          — mutating outer state from inside a trace
- ``device-put-in-loop``   — per-item H2D transfers in a Python loop
- ``host-time-in-jit``     — host clock reads / obs-plane calls under a trace
- ``lock-order``           — service/buffer lock acquired under a shard lock
- ``sharding-rule-bypass`` — NamedSharding/PartitionSpec built outside the
  partition-rule core (``parallel/partition.py``)
- ``lock-cycle``           — interprocedural ABBA cycle in the lock graph
- ``unguarded-shared-write`` — shared attribute mutated off its owning lock
- ``wire-magic-registry``  — frame magic/flag bit outside the declared table
- ``codec-asymmetry``      — pack/unpack format or field-count drift
- ``unchecked-frame``      — recv-rooted decode without error/crc containment
- ``flag-bit-collision``   — one flag-byte bit claimed by two extensions
- ``thread-crash-containment`` — Thread target that can die uncaught (or
  caught-but-uncounted); ``# jaxlint: contained-by=<handler>`` declares
  an audited wrapper
- ``span-terminal-missing`` — trace begin with an exception-edge path to
  exit that never reaches a commit/shed terminal
- ``ledger-conservation``  — admission-counter bump whose path to exit
  records no disposition and no hand-off
- ``collective-axis-unbound`` — psum/pmean/axis_index axis_name with no
  reachable shard_map binding, or an axis hand-spelled/undeclared;
  ``# jaxlint: axis-bound-by=<caller>`` declares an audited binder
- ``sharding-spec-drift``  — in/out_shardings/device_put spec reaching a
  raw sharding constructor through dataflow, or a tree re-placed under a
  different partition factory (implicit reshard)
- ``donation-alias``       — donate_argnums call whose donated argument
  aliases another argument or a live captured reference
- ``rng-ambient-stream``   — numpy/stdlib global-RNG draw, unseeded
  ctor, or wall-clock seed inside determinism-scoped code
- ``rng-stream-thread-escape`` — one Generator drawn from two
  thread-spawn targets without its own SeedSequence branch;
  ``# jaxlint: stream-owner=<Component.attr>`` declares a caller-owned
  branch
- ``rng-draw-count-drift`` — seeded stream drawn a path-dependent
  count per event (the PR-12 desync shape); only skip-before-RNG-use
  is clean

The last fifteen are PROGRAM-scope families implemented in
``lint/lockgraph.py`` (locks), ``lint/wiregraph.py`` (wire protocol),
``lint/failgraph.py`` (exception flow / ledger), ``lint/meshgraph.py``
(sharding & collectives) and ``lint/rnggraph.py`` (RNG provenance &
determinism — which also upgrades family 1 interprocedurally): they
analyze every module of a lint run together (cross-module call graph),
where everything above is per-module.

Every rule is a function ``(ModuleContext) -> list[Finding]`` registered in
``RULES``. Rules are deliberately conservative: a finding should be either
a true positive or a line whose suppression comment is itself useful
documentation. Branchy dataflow uses *all-paths* (intersection) merging so
an ``if/else`` that consumes a key once per arm never fires.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from d4pg_tpu.lint.context import (
    FunctionNode, JitBinding, ModuleContext, _int_tuple, dotted_name,
    call_kind, is_trace_wrapper_expr, last_part,
)
from d4pg_tpu.lint.findings import Finding

# --------------------------------------------------------------------------
# shared AST helpers
# --------------------------------------------------------------------------


def walk_own(node: ast.AST):
    """Walk ``node``'s subtree WITHOUT descending into nested functions —
    each function is analyzed in its own pass."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, FunctionNode):
            continue
        yield child
        yield from walk_own(child)


def all_functions(ctx: ModuleContext):
    for node in ast.walk(ctx.tree):
        if isinstance(node, FunctionNode):
            yield node


def _body_of(func: ast.AST) -> list[ast.stmt]:
    if isinstance(func, ast.Lambda):
        return [ast.Expr(value=func.body)]
    return func.body


def _bound_names(target: ast.expr) -> set[str]:
    """Names bound by an assignment target (tuple-aware)."""
    out: set[str] = set()
    for n in ast.walk(target):
        if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store, ast.Del)):
            out.add(n.id)
    return out


def _ordered(nodes):
    return sorted(nodes, key=lambda n: (n.lineno, n.col_offset))


# --------------------------------------------------------------------------
# a tiny sequential interpreter for dataflow-ish rules (R1, R4)
#
# Rules subclass SequentialRule and implement on_call / on_load; the driver
# walks statements in execution order, forks state at branches, merges with
# set-intersection (all-paths semantics), and runs loop bodies twice to
# catch cross-iteration hazards. State is a dict name -> info; rebinding a
# name always clears it.
# --------------------------------------------------------------------------


class SequentialRule:
    def __init__(self, ctx: ModuleContext):
        self.ctx = ctx
        self.findings: list[Finding] = []
        self._seen: set[tuple] = set()

    # -- overridables ------------------------------------------------------
    def on_call(self, call: ast.Call, state: dict) -> None: ...
    def on_load(self, name: ast.Name, state: dict) -> None: ...

    # -- driver ------------------------------------------------------------
    def emit(self, node: ast.AST, rule: str, msg: str) -> None:
        key = (node.lineno, node.col_offset, rule, msg)
        if key not in self._seen:
            self._seen.add(key)
            self.findings.append(Finding(
                self.ctx.path, node.lineno, node.col_offset, rule, msg))

    def run_function(self, func: ast.AST) -> None:
        self._exec_block(_body_of(func), {})

    def _visit_expr(self, expr: ast.AST, state: dict) -> None:
        """Calls and loads in source order; nested defs are other scopes."""
        nodes = [n for n in ast.walk(expr)
                 if isinstance(n, (ast.Call, ast.Name, ast.Lambda))]
        skip: set[int] = set()
        for n in nodes:
            if isinstance(n, ast.Lambda):
                for inner in ast.walk(n):
                    skip.add(id(inner))
        def order(n):
            # a call's effect (key consumption, donation) lands when the
            # call completes: order it by END position so loads of its own
            # arguments are processed first
            if isinstance(n, ast.Call):
                return (n.end_lineno or n.lineno,
                        n.end_col_offset or n.col_offset)
            return (n.lineno, n.col_offset)

        for n in sorted((n for n in nodes if id(n) not in skip), key=order):
            if isinstance(n, ast.Call):
                self.on_call(n, state)
            elif isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                self.on_load(n, state)

    def _exec_block(self, body: list[ast.stmt], state: dict) -> None:
        for stmt in body:
            self._exec_stmt(stmt, state)

    def _merge(self, state: dict, branches: list[dict]) -> None:
        """All-paths merge: keep entries present in EVERY branch outcome."""
        state.clear()
        if not branches:
            return
        common = set(branches[0])
        for b in branches[1:]:
            common &= set(b)
        for k in common:
            state[k] = branches[0][k]

    def _exec_stmt(self, stmt: ast.stmt, state: dict) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # separate scope; analyzed in its own pass
        if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            if stmt.value is not None:
                self._visit_expr(stmt.value, state)
            targets = (stmt.targets if isinstance(stmt, ast.Assign)
                       else [stmt.target])
            for t in targets:
                for name in _bound_names(t):
                    state.pop(name, None)
            return
        if isinstance(stmt, ast.If):
            self._visit_expr(stmt.test, state)
            a, b = dict(state), dict(state)
            self._exec_block(stmt.body, a)
            self._exec_block(stmt.orelse, b)
            self._merge(state, [a, b])
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._visit_expr(stmt.iter, state)
            for name in _bound_names(stmt.target):
                state.pop(name, None)
            # run the body twice: the second pass catches hazards that only
            # appear across iterations (key consumed, never re-split)
            self._exec_block(stmt.body, state)
            for name in _bound_names(stmt.target):
                state.pop(name, None)
            self._exec_block(stmt.body, state)
            self._exec_block(stmt.orelse, state)
            return
        if isinstance(stmt, ast.While):
            self._visit_expr(stmt.test, state)
            self._exec_block(stmt.body, state)
            self._exec_block(stmt.body, state)
            self._exec_block(stmt.orelse, state)
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._visit_expr(item.context_expr, state)
                if item.optional_vars is not None:
                    for name in _bound_names(item.optional_vars):
                        state.pop(name, None)
            self._exec_block(stmt.body, state)
            return
        if isinstance(stmt, ast.Try):
            a = dict(state)
            self._exec_block(stmt.body, a)
            outcomes = [a]
            for h in stmt.handlers:
                b = dict(state)
                self._exec_block(h.body, b)
                outcomes.append(b)
            self._merge(state, outcomes)
            self._exec_block(stmt.orelse, state)
            self._exec_block(stmt.finalbody, state)
            return
        # leaf statements: Expr, Return, Raise, Assert, Delete, ...
        for value in ast.iter_child_nodes(stmt):
            if isinstance(value, ast.expr):
                self._visit_expr(value, state)
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                for name in _bound_names(t):
                    state.pop(name, None)


# --------------------------------------------------------------------------
# R1: prng-key-reuse
# --------------------------------------------------------------------------

_SAMPLERS = {
    "ball", "bernoulli", "beta", "binomial", "bits", "categorical", "cauchy",
    "chisquare", "choice", "dirichlet", "double_sided_maxwell", "exponential",
    "gamma", "generalized_normal", "geometric", "gumbel", "laplace",
    "loggamma", "logistic", "lognormal", "maxwell", "multivariate_normal",
    "normal", "orthogonal", "pareto", "permutation", "poisson", "rademacher",
    "randint", "rayleigh", "shuffle", "t", "triangular", "truncated_normal",
    "uniform", "wald", "weibull_min",
}
_NP_BASES = {"np", "numpy", "onp"}


def _random_call(call: ast.Call) -> str | None:
    """'normal' if this is a jax.random sampler call, else None."""
    dotted = dotted_name(call.func)
    if not dotted:
        return None
    parts = dotted.split(".")
    if parts[0] in _NP_BASES:
        return None  # numpy's random API takes no key
    fn = parts[-1]
    if fn not in _SAMPLERS:
        return None
    if "random" in parts[:-1] or parts[0] in {"jr", "jrandom"}:
        return fn
    return None


class _KeyReuse(SequentialRule):
    def on_call(self, call: ast.Call, state: dict) -> None:
        fn = _random_call(call)
        if fn is None or not call.args:
            return
        key = call.args[0]
        if not isinstance(key, ast.Name):
            return
        prior = state.get(key.id)
        if prior is not None:
            pline, pfn = prior
            self.emit(
                call, "prng-key-reuse",
                f"key '{key.id}' already consumed by jax.random.{pfn} at "
                f"line {pline}; split() or fold_in() before reusing it")
        else:
            state[key.id] = (call.lineno, fn)


def rule_prng_key_reuse(ctx: ModuleContext) -> list[Finding]:
    checker = _KeyReuse(ctx)
    for func in all_functions(ctx):
        checker.run_function(func)
    return checker.findings


# --------------------------------------------------------------------------
# R2: host-sync-in-jit
# --------------------------------------------------------------------------

_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_CONVERTERS = {"asarray", "array"}


def _static_param_names(func: ast.AST) -> set[str]:
    """Parameters marked static by a jit decorator: concrete Python values
    at trace time, so concretizing them (float()/int()) is legitimate."""
    if isinstance(func, ast.Lambda):
        return set()
    params = [a.arg for a in (*func.args.posonlyargs, *func.args.args)]
    out: set[str] = set()
    for dec in func.decorator_list:
        if not (isinstance(dec, ast.Call) and is_trace_wrapper_expr(dec)):
            continue
        kwargs = {k.arg: k.value for k in dec.keywords if k.arg}
        for i in _int_tuple(kwargs.get("static_argnums")):
            if i < len(params):
                out.add(params[i])
        names = kwargs.get("static_argnames")
        if isinstance(names, ast.Constant) and isinstance(names.value, str):
            out.add(names.value)
        elif isinstance(names, (ast.Tuple, ast.List)):
            out.update(e.value for e in names.elts
                       if isinstance(e, ast.Constant)
                       and isinstance(e.value, str))
    return out


def _root_name(expr: ast.expr) -> str | None:
    while isinstance(expr, ast.Attribute):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def rule_host_sync_in_jit(ctx: ModuleContext) -> list[Finding]:
    findings: list[Finding] = []

    def emit(node, msg):
        findings.append(Finding(
            ctx.path, node.lineno, node.col_offset, "host-sync-in-jit", msg))

    for func in all_functions(ctx):
        if not ctx.is_traced(func):
            continue
        static_names = _static_param_names(func)
        for node in walk_own(func):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _SYNC_METHODS:
                emit(node, f".{f.attr}() inside traced code forces a "
                           "host-device sync (or a concretization error)")
                continue
            dotted = dotted_name(f) or ""
            parts = dotted.split(".")
            if (len(parts) > 1 and parts[0] in _NP_BASES
                    and parts[-1] in _CONVERTERS):
                emit(node, f"{dotted}() inside traced code pulls the value "
                           "to host; use jnp instead")
            elif parts[-1] == "device_get" and parts[0] in {"jax", "device_get"}:
                emit(node, "jax.device_get() inside traced code is a "
                           "host-device sync")
            elif (isinstance(f, ast.Name) and f.id in {"float", "int", "bool"}
                    and node.args
                    and not isinstance(node.args[0], ast.Constant)
                    and _root_name(node.args[0]) not in static_names):
                emit(node, f"{f.id}() on a traced value forces concretization;"
                           " keep it an array (jnp.asarray / astype)")
    return findings


# --------------------------------------------------------------------------
# R3: recompile-hazard
# --------------------------------------------------------------------------


def _is_jit_or_pmap_call(call: ast.Call) -> bool:
    if call_kind(call) != "wrapper":
        return False
    target = call.func
    if last_part(dotted_name(target)) == "partial" and call.args:
        target = call.args[0]
    return last_part(dotted_name(target)) in {"jit", "pmap"}


def rule_recompile_hazard(ctx: ModuleContext) -> list[Finding]:
    findings: list[Finding] = []

    def emit(node, msg):
        findings.append(Finding(
            ctx.path, node.lineno, node.col_offset, "recompile-hazard", msg))

    # parent map for loop-ancestry and loop-variable checks
    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node

    def enclosing_loop_vars(node: ast.AST) -> set[str]:
        """Induction variables of For loops between node and its function."""
        out: set[str] = set()
        cur = parents.get(node)
        while cur is not None and not isinstance(cur, FunctionNode):
            if isinstance(cur, ast.For):
                out |= _bound_names(cur.target)
            cur = parents.get(cur)
        return out

    def inside_loop(node: ast.AST) -> bool:
        cur = parents.get(node)
        while cur is not None and not isinstance(cur, FunctionNode):
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                return True
            cur = parents.get(cur)
        return False

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        # (a) jit() created inside a loop: a fresh wrapper (and cache) per
        # iteration — nothing is ever a cache hit
        if _is_jit_or_pmap_call(node) and inside_loop(node):
            emit(node, "jax.jit/pmap created inside a loop builds a fresh "
                       "compilation cache every iteration; hoist it out")
        # (b) jax.jit(f)(x): wrapper discarded after one call
        if (isinstance(node.func, ast.Call)
                and _is_jit_or_pmap_call(node.func)):
            emit(node, "jax.jit(f)(...) compiles and discards the wrapper; "
                       "bind the jitted function once and reuse it")
        # (c) hazards at call sites of known jit bindings with static args
        if isinstance(node.func, ast.Name):
            binding = ctx.jit_bindings.get(node.func.id)
            if binding is not None and binding.static_argnums:
                loop_vars = enclosing_loop_vars(node)
                for pos in binding.static_argnums:
                    if pos >= len(node.args):
                        continue
                    arg = node.args[pos]
                    if isinstance(arg, ast.Name) and arg.id in loop_vars:
                        emit(arg, f"loop variable '{arg.id}' passed as "
                                  f"static arg {pos} of '{binding.name}': "
                                  "recompiles every iteration")
                    elif isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                        emit(arg, f"unhashable literal as static arg {pos} "
                                  f"of '{binding.name}': jit cache lookup "
                                  "raises or always misses")
    return findings


# --------------------------------------------------------------------------
# R4: use-after-donation
# --------------------------------------------------------------------------


class _UseAfterDonation(SequentialRule):
    def on_call(self, call: ast.Call, state: dict) -> None:
        # reads inside the call expression itself happen before donation,
        # so on_load (driven in source order) has already seen them
        if not isinstance(call.func, ast.Name):
            return
        binding: JitBinding | None = self.ctx.jit_bindings.get(call.func.id)
        if binding is None or not binding.donate_argnums:
            return
        for pos in binding.donate_argnums:
            if pos < len(call.args) and isinstance(call.args[pos], ast.Name):
                state[call.args[pos].id] = (call.lineno, binding.name)

    def on_load(self, name: ast.Name, state: dict) -> None:
        info = state.get(name.id)
        if info is not None:
            dline, gname = info
            self.emit(
                name, "use-after-donation",
                f"'{name.id}' was donated to '{gname}' at line {dline}; its "
                "buffer is gone — rebind the result or drop the reference")


def rule_use_after_donation(ctx: ModuleContext) -> list[Finding]:
    checker = _UseAfterDonation(ctx)
    for func in all_functions(ctx):
        checker.run_function(func)
    # module-level straight-line code can donate too
    checker._exec_block(
        [s for s in ctx.tree.body
         if not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef))],
        {})
    return checker.findings


# --------------------------------------------------------------------------
# R5: tracer-leak
# --------------------------------------------------------------------------

_MUTATORS = {"append", "extend", "add", "insert", "update", "setdefault",
             "pop", "popleft", "appendleft", "remove", "clear"}


def _local_names(func: ast.AST) -> set[str]:
    out: set[str] = set()
    if not isinstance(func, ast.Lambda):
        args = func.args
    else:
        args = func.args
    for a in (*args.posonlyargs, *args.args, *args.kwonlyargs):
        out.add(a.arg)
    if args.vararg:
        out.add(args.vararg.arg)
    if args.kwarg:
        out.add(args.kwarg.arg)
    for node in walk_own(func):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            out.add(node.id)
    return out


def rule_tracer_leak(ctx: ModuleContext) -> list[Finding]:
    findings: list[Finding] = []

    def emit(node, msg):
        findings.append(Finding(
            ctx.path, node.lineno, node.col_offset, "tracer-leak", msg))

    for func in all_functions(ctx):
        if not ctx.is_traced(func):
            continue
        locals_ = _local_names(func)
        # container mutators return None, so a real mutation is a bare
        # expression statement; a used return value means it's an ordinary
        # function that merely shares a name with list.insert/dict.update
        bare_calls = {
            id(n.value) for n in walk_own(func)
            if isinstance(n, ast.Expr) and isinstance(n.value, ast.Call)
        }
        for node in walk_own(func):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                kw = "global" if isinstance(node, ast.Global) else "nonlocal"
                emit(node, f"'{kw}' write inside traced code leaks tracers "
                           "into outer state (stale after the first trace)")
            elif isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Attribute):
                        emit(t, "attribute assignment inside traced code "
                                "stores a tracer on a host object; thread "
                                "state through the function instead")
                    elif (isinstance(t, ast.Subscript)
                            and isinstance(t.value, ast.Name)
                            and t.value.id not in locals_):
                        emit(t, f"writing into closed-over '{t.value.id}' "
                                "inside traced code leaks tracers")
            elif (isinstance(node, ast.Call)
                    and id(node) in bare_calls
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MUTATORS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id not in locals_):
                emit(node, f"mutating closed-over '{node.func.value.id}."
                           f"{node.func.attr}(...)' inside traced code leaks "
                           "tracers (and re-runs only at trace time)")
    return findings


# --------------------------------------------------------------------------
# R6: device-put-in-loop
# --------------------------------------------------------------------------


def rule_device_put_in_loop(ctx: ModuleContext) -> list[Finding]:
    """``jax.device_put`` inside a Python loop: per-item H2D transfers
    serialize against dispatch and pay per-call overhead every iteration —
    the exact ingest anti-pattern the block drain removed
    (``replay/fused_buffer.py``: coalesce rows into a block and transfer
    ONCE). Loops here are ``for``/``while`` statements in the same
    function (a comprehension builds one value and a nested function is
    its own scope, analyzed separately)."""
    findings: list[Finding] = []

    parents: dict[ast.AST, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node

    def inside_loop(node: ast.AST) -> bool:
        cur = parents.get(node)
        while cur is not None and not isinstance(cur, FunctionNode):
            if isinstance(cur, (ast.For, ast.AsyncFor, ast.While)):
                return True
            cur = parents.get(cur)
        return False

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = dotted_name(node.func) or ""
        parts = dotted.split(".")
        if parts[-1] != "device_put":
            continue
        if len(parts) > 1 and parts[0] not in {"jax"}:
            continue  # some_obj.device_put: not the jax entry point
        if inside_loop(node):
            findings.append(Finding(
                ctx.path, node.lineno, node.col_offset, "device-put-in-loop",
                "device_put inside a loop transfers per item; coalesce the "
                "rows into one block and transfer once (see the block "
                "drain in replay/fused_buffer.py)"))
    return findings


# --------------------------------------------------------------------------
# R10: host-time-in-jit
# --------------------------------------------------------------------------

# time-module entry points whose value is a HOST clock read: under a
# trace they execute once at trace time and bake into the jaxpr as a
# constant — every later call of the compiled function reports the same
# "timestamp", silently. (The observability plane makes this hazard
# live: span stamps are cheap enough that someone WILL eventually try
# to time a jitted body from inside.)
_TIME_FNS = {"time", "time_ns", "perf_counter", "perf_counter_ns",
             "monotonic", "monotonic_ns", "process_time",
             "process_time_ns", "thread_time", "thread_time_ns"}
# bare-name clock reads distinctive enough to flag without a module
# root (`from time import perf_counter`); bare `time()` stays unflagged
# (too generic a name to claim).
_TIME_BARE = _TIME_FNS - {"time"}
# obs-plane entry points (d4pg_tpu/obs): recorder spans and registry
# mutations are host side effects — traced code calling them records
# once at trace time and never again (the tracer-leak failure mode,
# with a clock attached).
_OBS_FNS = {"record_span", "mark_grad", "mark_committed", "mark_through",
            "terminal_shed", "shed_dropped", "new_trace_id", "record_event",
            "latency_block"}
_OBS_METHODS = {"inc", "observe"}
_OBS_RECV_HINTS = ("registry", "counter", "gauge", "histogram", "metric",
                   "recorder", "tracer")


def rule_host_time_in_jit(ctx: ModuleContext) -> list[Finding]:
    """Flag host clock reads (``time.time()``/``perf_counter()``/...)
    and observability-plane calls (trace spans, registry counters)
    inside jit-traced code: they run at TRACE time, bake into the jaxpr
    as constants, and silently lie on every compiled call. Move the
    measurement to the dispatch site (bracket the jitted call), or
    thread real timestamps in as arguments."""
    findings: list[Finding] = []

    def emit(node, msg):
        findings.append(Finding(
            ctx.path, node.lineno, node.col_offset, "host-time-in-jit", msg))

    for func in all_functions(ctx):
        if not ctx.is_traced(func):
            continue
        for node in walk_own(func):
            if not isinstance(node, ast.Call):
                continue
            dotted = dotted_name(node.func) or ""
            parts = dotted.split(".")
            fn = parts[-1]
            if fn in _TIME_FNS and len(parts) > 1 and parts[0] == "time":
                emit(node, f"{dotted}() inside traced code reads the host "
                           "clock at TRACE time and bakes it in as a "
                           "constant — every compiled call reports the "
                           "same timestamp; time the dispatch site "
                           "instead")
            elif fn in _TIME_BARE and len(parts) == 1:
                emit(node, f"{fn}() inside traced code reads the host "
                           "clock at TRACE time (constant thereafter); "
                           "time the dispatch site instead")
            elif fn in _OBS_FNS:
                emit(node, f"observability call {dotted}() inside traced "
                           "code runs ONCE at trace time — the span/"
                           "event it records never fires again; hoist it "
                           "to the dispatch site")
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _OBS_METHODS):
                # receiver may be a name chain (counter.inc) or a call
                # chain (REGISTRY.counter("x").inc); take whichever
                # dotted path exists and look for obs-plane hints
                recv = node.func.value
                recv_dotted = dotted_name(
                    recv.func if isinstance(recv, ast.Call) else recv) or ""
                if any(h in part.lower() for part in recv_dotted.split(".")
                       for h in _OBS_RECV_HINTS):
                    emit(node, f"registry mutation .{node.func.attr}() on "
                               f"'{recv_dotted}' inside traced code runs "
                               "ONCE at trace time — the counter silently "
                               "stops counting; hoist it to the dispatch "
                               "site")
    return findings


# --------------------------------------------------------------------------
# R7: lock-order
# --------------------------------------------------------------------------

# The sharded ingest plane's locking discipline (distributed/
# replay_service.py): shard/ring locks are LEAF locks. The commit thread
# holds the buffer or service lock and may wait for shard work to land;
# a thread that takes the buffer/service lock while already inside a
# shard/ring lock closes the classic ABBA cycle. Tiers by attribute name
# (conservative: only these exact suffixes participate):
_LEAF_LOCKS = {"cond", "_cond", "ring_lock", "shard_lock", "_ring_locks",
               "_shard_locks"}
_OUTER_LOCKS = {"_buffer_lock", "_lock", "_commit_cond"}


def _lock_tier(expr: ast.expr) -> str | None:
    """'leaf' / 'outer' / None for a with-item or .acquire() receiver."""
    # unwrap subscripts: with self._ring_locks[i]: ...
    while isinstance(expr, ast.Subscript):
        expr = expr.value
    name = last_part(dotted_name(expr) or "")
    if name in _LEAF_LOCKS:
        return "leaf"
    if name in _OUTER_LOCKS:
        return "outer"
    return None


def rule_lock_order(ctx: ModuleContext) -> list[Finding]:
    """Flags acquiring a buffer/service-tier lock while holding a
    shard/ring-tier (leaf) lock — the deadlock shape the sharded ingest
    refactor introduces. Detects both ``with`` nesting and bare
    ``.acquire()`` calls lexically inside a leaf ``with`` block, within
    one function (cross-function flows are the suppression-documented
    exception)."""
    findings: list[Finding] = []

    def emit(node, held: str):
        findings.append(Finding(
            ctx.path, node.lineno, node.col_offset, "lock-order",
            f"outer-tier lock acquired while holding leaf lock '{held}' — "
            "shard/ring locks are leaf locks; take the buffer/service "
            "lock first or split the critical section"))

    def scan(body: list[ast.stmt], held: str | None) -> None:
        for stmt in body:
            inner_held = held
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    tier = _lock_tier(item.context_expr)
                    if tier == "outer" and held is not None:
                        emit(item.context_expr, held)
                    elif tier == "leaf":
                        nm = last_part(
                            dotted_name(
                                item.context_expr.value
                                if isinstance(item.context_expr,
                                              ast.Subscript)
                                else item.context_expr) or "")
                        inner_held = nm or "leaf"
                scan(stmt.body, inner_held)
                continue
            if isinstance(stmt, FunctionNode):
                continue  # new scope, analyzed by its own pass
            if held is not None:
                for node in walk_own(stmt):
                    if (isinstance(node, ast.Call)
                            and isinstance(node.func, ast.Attribute)
                            and node.func.attr == "acquire"
                            and _lock_tier(node.func.value) == "outer"):
                        emit(node, held)
            # generic recursion into compound statements
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, attr, None)
                if isinstance(sub, list) and sub \
                        and isinstance(sub[0], ast.stmt):
                    scan(sub, held)
            for handler in getattr(stmt, "handlers", []) or []:
                scan(handler.body, held)

    for func in all_functions(ctx):
        scan(_body_of(func), None)
    scan([s for s in ctx.tree.body if not isinstance(s, FunctionNode)], None)
    return findings


# --------------------------------------------------------------------------
# R15: sharding-rule-bypass
# --------------------------------------------------------------------------

# The partition-rule core (parallel/partition.py) is the single source of
# sharding truth: every layout the package places on an array resolves
# through its regex rule table (or a factory wrapping it), so ONE
# printable table owns every placement decision. A raw constructor call
# anywhere else re-opens the hand-wired-axis drift the core closed.
_SHARDING_CTORS = {"NamedSharding", "PartitionSpec"}
_SHARDING_MODULES = {"jax.sharding"}
# dotted-call roots distinctive enough to claim without import tracking
_SHARDING_ROOTS = {"jax", "sharding", "partition"}


def rule_sharding_rule_bypass(ctx: ModuleContext) -> list[Finding]:
    """Flag ``NamedSharding(...)`` / ``PartitionSpec(...)`` construction —
    including import aliases (``PartitionSpec as P``, ``partition.PS``) —
    anywhere outside ``parallel/partition.py``. Layouts come from the
    rule core (``partition.spec``/``sharding``/``match_partition_rules``
    or a ``*_sharding`` factory); a raw constructor bypasses the table."""
    if ctx.path.replace("\\", "/").endswith("parallel/partition.py"):
        return []  # the rule core is where the constructors BELONG

    aliases: dict[str, str] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod in _SHARDING_MODULES or mod.endswith("parallel.partition"):
                for a in node.names:
                    if a.name in _SHARDING_CTORS or a.name == "PS":
                        canon = ("PartitionSpec" if a.name == "PS"
                                 else a.name)
                        aliases[a.asname or a.name] = canon
        elif (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            # re-aliasing (P = partition.PS): calls through it bypass too
            src = dotted_name(node.value) or ""
            if "." in src and last_part(src) in _SHARDING_CTORS | {"PS"}:
                aliases[node.targets[0].id] = (
                    "PartitionSpec" if last_part(src) == "PS"
                    else last_part(src))

    findings: list[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = dotted_name(node.func) or ""
        parts = dotted.split(".")
        ctor = None
        if len(parts) == 1:
            ctor = aliases.get(parts[0])
        elif parts[0] in _SHARDING_ROOTS:
            if parts[-1] in _SHARDING_CTORS:
                ctor = parts[-1]
            elif parts[-1] == "PS" and parts[0] == "partition":
                ctor = "PartitionSpec"
        if ctor is None:
            continue
        findings.append(Finding(
            ctx.path, node.lineno, node.col_offset, "sharding-rule-bypass",
            f"{ctor} constructed outside parallel/partition.py — resolve "
            "the layout through the partition-rule core (partition.spec/"
            "sharding/match_partition_rules or a *_sharding factory) so "
            "the rule table stays the single source of placement truth"))
    return findings


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Rule:
    id: str
    summary: str
    check: object  # (ModuleContext) -> list[Finding]
    # 'module' rules see one file at a time; 'program' rules (the lock
    # graph) run ONCE over every analyzed module together — the engine
    # dispatches them to lint/lockgraph.py instead of the per-file loop.
    scope: str = "module"


def _program_rule(rule_id: str):
    """Single-module fallback so ``lint_source`` (fixtures, snippets)
    drives the program families through the same registry entry; whole
    trees go through ``engine.lint_paths``'s one-shot program pass."""
    def check(ctx: ModuleContext) -> list[Finding]:
        from d4pg_tpu.lint import lockgraph

        return lockgraph.analyze([ctx], rules=[rule_id]).findings

    return check


def _wire_rule(rule_id: str):
    """Same single-module fallback for the wire-protocol families
    (``lint/wiregraph.py``)."""
    def check(ctx: ModuleContext) -> list[Finding]:
        from d4pg_tpu.lint import wiregraph

        return wiregraph.analyze([ctx], rules=[rule_id]).findings

    return check


def _fail_rule(rule_id: str):
    """Same single-module fallback for the exception-flow families
    (``lint/failgraph.py``)."""
    def check(ctx: ModuleContext) -> list[Finding]:
        from d4pg_tpu.lint import failgraph

        return failgraph.analyze([ctx], rules=[rule_id]).findings

    return check


def _mesh_rule(rule_id: str):
    """Same single-module fallback for the sharding/collective families
    (``lint/meshgraph.py``)."""
    def check(ctx: ModuleContext) -> list[Finding]:
        from d4pg_tpu.lint import meshgraph

        return meshgraph.analyze([ctx], rules=[rule_id]).findings

    return check


def _rng_rule(rule_id: str):
    """Same single-module fallback for the RNG-provenance families
    (``lint/rnggraph.py``)."""
    def check(ctx: ModuleContext) -> list[Finding]:
        from d4pg_tpu.lint import rnggraph

        return rnggraph.analyze([ctx], rules=[rule_id]).findings

    return check


RULES: dict[str, Rule] = {r.id: r for r in [
    Rule("prng-key-reuse",
         "same PRNG key consumed by two jax.random samplers without an "
         "intervening split/fold_in",
         rule_prng_key_reuse),
    Rule("host-sync-in-jit",
         ".item()/float()/np.asarray/device_get/block_until_ready inside "
         "traced code",
         rule_host_sync_in_jit),
    Rule("recompile-hazard",
         "jit built in a loop, jit(f)(x) immediate calls, value-varying or "
         "unhashable static args",
         rule_recompile_hazard),
    Rule("use-after-donation",
         "reading an argument after a donate_argnums call consumed its "
         "buffer",
         rule_use_after_donation),
    Rule("tracer-leak",
         "traced code mutating outer state (global/nonlocal/attribute/"
         "closure writes)",
         rule_tracer_leak),
    Rule("device-put-in-loop",
         "jax.device_put called inside a Python loop — per-item H2D; "
         "coalesce into a block and transfer once",
         rule_device_put_in_loop),
    Rule("host-time-in-jit",
         "time.time()/perf_counter()/trace-span/registry calls inside "
         "traced code — they run once at trace time and silently lie",
         rule_host_time_in_jit),
    Rule("lock-order",
         "buffer/service lock acquired while holding a shard/ring leaf "
         "lock — the sharded-ingest deadlock shape",
         rule_lock_order),
    Rule("sharding-rule-bypass",
         "NamedSharding/PartitionSpec (or an alias: P, partition.PS) "
         "constructed outside parallel/partition.py — layouts resolve "
         "through the partition-rule table, not hand-wired axes",
         rule_sharding_rule_bypass),
    Rule("lock-cycle",
         "cycle in the interprocedural held-while-acquiring lock graph "
         "(ABBA across any number of calls) — see lint/lockgraph.py",
         _program_rule("lock-cycle"), scope="program"),
    Rule("unguarded-shared-write",
         "attribute written without the lock every other access holds "
         "(ownership inferred; declare `# jaxlint: guarded-by=<lock>`)",
         _program_rule("unguarded-shared-write"), scope="program"),
    Rule("wire-magic-registry",
         "0xD4xx magic or flag bit packed into a frame but absent from / "
         "re-declared outside the declared registry (core/wire.py); "
         "seed-derivation literals are exempt",
         _wire_rule("wire-magic-registry"), scope="program"),
    Rule("codec-asymmetry",
         "pack/unpack format not a field segment of its magic's declared "
         "header, arg/target count drift, *_SIZE constant != calcsize, or "
         "a magic packed but never unpacked",
         _wire_rule("codec-asymmetry"), scope="program"),
    Rule("unchecked-frame",
         "socket-facing decode (recv -> unpack/np.load/np.frombuffer) "
         "without struct.error/ValueError containment, or payload use "
         "before the declared crc32 check",
         _wire_rule("unchecked-frame"), scope="program"),
    Rule("flag-bit-collision",
         "two extensions claiming the same bit of the same plane's flag "
         "byte — see core/wire.py for the allocations",
         _wire_rule("flag-bit-collision"), scope="program"),
    Rule("thread-crash-containment",
         "threading.Thread target that can die on an uncaught raise, or "
         "whose broad handler swallows the crash uncounted — declare "
         "`# jaxlint: contained-by=<handler>` for wrapped targets",
         _fail_rule("thread-crash-containment"), scope="program"),
    Rule("span-terminal-missing",
         "trace begin whose exception edges can exit the frame without a "
         "commit/shed terminal — the static zero-orphan invariant",
         _fail_rule("span-terminal-missing"), scope="program"),
    Rule("ledger-conservation",
         "frame-admission counter bump with a path to exit that records "
         "neither a disposition counter nor a terminal hand-off",
         _fail_rule("ledger-conservation"), scope="program"),
    Rule("collective-axis-unbound",
         "psum/pmean/all_gather/axis_index axis_name with no reachable "
         "shard_map binding, or an axis hand-spelled/undeclared — "
         "declare `# jaxlint: axis-bound-by=<caller>` for helpers bound "
         "by their callers",
         _mesh_rule("collective-axis-unbound"), scope="program"),
    Rule("sharding-spec-drift",
         "in_shardings/out_shardings/device_put spec that resolves "
         "through dataflow to a raw sharding constructor outside "
         "parallel/partition.py, or a tree re-placed under a different "
         "partition factory (implicit reshard)",
         _mesh_rule("sharding-spec-drift"), scope="program"),
    Rule("donation-alias",
         "donate_argnums call site whose donated argument aliases "
         "another argument or a live captured reference the call never "
         "rebinds — the replica deep-copy defect, statically",
         _mesh_rule("donation-alias"), scope="program"),
    Rule("rng-ambient-stream",
         "numpy module-level global draw, stdlib random.* draw, "
         "unseeded default_rng()/RandomState(), or wall-clock-derived "
         "seed reachable from determinism-scoped code (fleet/chaos/"
         "traffic/sampler/ledger paths)",
         _rng_rule("rng-ambient-stream"), scope="program"),
    Rule("rng-stream-thread-escape",
         "one Generator drawn from two distinct thread-spawn targets "
         "without its own SeedSequence branch — declare "
         "`# jaxlint: stream-owner=<Component.attr>` for caller-owned "
         "branches",
         _rng_rule("rng-stream-thread-escape"), scope="program"),
    Rule("rng-draw-count-drift",
         "seeded stream drawn a path-dependent count per event — the "
         "PR-12 backpressure desync shape; clean only under the "
         "documented skip-before-RNG-use idiom",
         _rng_rule("rng-draw-count-drift"), scope="program"),
]}
