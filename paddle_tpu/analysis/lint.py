"""Source-level AST linter for tracer hazards.

Usage::

    python -m paddle_tpu.analysis.lint paddle_tpu/ scripts/ [...]
        [--allowlist FILE] [--no-default-allowlist] [--allow-stale]

The linter finds **syntactic jit scopes** — functions decorated with
``@jax.jit`` / ``@to_static`` / ``partial(jax.jit, ...)``, functions (or
lambdas) passed directly to ``jax.jit`` / ``jax.lax.scan`` /
``while_loop`` / ``cond`` / ``fori_loop`` / ``switch`` / ``jax.vmap`` /
``jax.grad`` / ``jax.checkpoint`` / ``shard_map``, and every function
lexically nested inside one — and applies a local taint dataflow where
the scope's PARAMETERS are the traced values. Rules:

- **H101 host sync**: ``.numpy()`` / ``.item()`` / ``.tolist()`` inside
  a jit scope — a device round-trip per trace, and a concretization
  error on real tracers.
- **H102 host scalar cast**: ``float(x)`` / ``int(x)`` / ``bool(x)``
  on a TAINTED value inside a jit scope (static python config stays
  unflagged because it never touches a parameter).
- **H103 numpy on traced**: ``np.*(...)`` with a tainted argument
  inside a jit scope — silently constant-folds the tracer or raises.
- **H104 traced control flow**: Python ``if`` / ``while`` whose test is
  tainted — value-dependent host branching a trace bakes in silently.
  ``x is None`` / ``isinstance`` / ``.shape`` / ``.ndim`` / ``.dtype``
  / ``len()`` neutralize taint (static under tracing).
- **H105 mutable default**: a ``[]`` / ``{}`` / ``set()`` default
  argument anywhere (not jit-specific, but the classic shared-state
  footgun) .
- **H106 wall-clock in jit scope**: ``time.time()`` /
  ``time.perf_counter()`` / ``time.monotonic()`` (and their ``_ns``
  forms, incl. bare from-imports) inside a jit scope — the timestamp
  constant-folds into the trace at compile time, so the "measurement"
  silently reports the tracing wall clock forever after.
  Instrumentation belongs at quantum/step boundaries on the host
  (``paddle_tpu.obs``), never inside the compiled program.
- **H107 metric mutation in jit scope** (companion to H106):
  ``.inc(`` / ``.observe(`` / ``.set(`` — the obs registry's mutation
  surface — inside a jit scope. The registry is host-side dict state:
  under tracing the mutation runs ONCE at compile time and never
  again, so the "metric" silently freezes at its tracing value.
  jax's functional array update ``x.at[i].set(v)`` is recognized and
  exempt.

Rules H108-H110 invert the scope: they scan **host** (non-jit) code
for *implicit device→host sync escapes* — the silent blocking
transfers the static cost model's host-gap estimate exists to kill
(ROADMAP item 2). Host taint seeds are DIRECT jax values (results of
``jnp.*`` / ``jax.numpy`` / ``jax.random`` / ``jax.lax`` /
``jax.device_put`` calls), not function parameters and not ``._value``
reads — the eager Tensor wrapper's contract is host semantics and its
conversion points are the audited, explicit sync surface:

- **H108 host scalar coercion**: a bare ``.item()`` call (on anything
  but an explicit ``np``/``numpy`` receiver), or ``float()`` /
  ``int()`` / ``bool()`` over a jax-tainted value, in host code — each
  one is a synchronous device round-trip the profiler never sees.
- **H109 numpy over jax value**: ``np.asarray`` / ``np.array`` / any
  ``np.*`` call with a jax-tainted argument in host code — an implicit
  blocking transfer hiding behind a type conversion.
- **H110 sync barrier in library code**: ``.block_until_ready()`` /
  ``jax.block_until_ready(...)`` anywhere in a file that is not
  bench/test code (path has a ``tests`` segment or a ``bench*`` /
  ``test*`` / ``conftest*`` basename) — a hard device barrier belongs
  in measurement harnesses, never in the serving/runtime libraries.

Known limits (by design, to stay fast and false-positive-light): the
scope detection is lexical per module — a module-level helper that is
only CALLED from inside a jitted closure is not scanned (no
inter-procedural call graph), and taint does not flow through
attribute stores or container mutation. The repo gate in
tests/test_analysis_lint.py runs this over ``paddle_tpu/`` AND
``scripts/`` with the checked-in allowlist next to this file, so every
NEW hazard fails tier-1 — and stale allowlist entries fail it too (by
default; ``--allow-stale`` opts out), so the list can only shrink.
"""
from __future__ import annotations

import ast
import os
import sys

__all__ = ["LintViolation", "lint_source", "lint_paths",
           "load_allowlist", "DEFAULT_ALLOWLIST"]

DEFAULT_ALLOWLIST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "lint_allowlist.txt")

RULES = {
    "H101": "host sync (.numpy()/.item()/.tolist()) inside a jit scope",
    "H102": "host scalar cast (float/int/bool) of a traced value",
    "H103": "np.* call on a traced value inside a jit scope",
    "H104": "Python if/while on a traced value inside a jit scope",
    "H105": "mutable default argument",
    "H106": "wall-clock read (time.time/perf_counter/monotonic) inside "
            "a jit scope — constant-folds into the trace",
    "H107": "metric mutation (.inc/.observe/.set) inside a jit scope — "
            "runs once at trace time, then silently freezes",
    "H108": "implicit device->host sync in host code (bare .item() or "
            "float/int/bool over a jax value) — a blocking transfer "
            "no profiler hook sees",
    "H109": "np.* over a jax value in host code — an implicit "
            "device->host transfer hiding behind a type conversion",
    "H110": "block_until_ready outside bench/test code — a hard "
            "device-sync barrier in library code",
}

# host-taint seeds for H108/H109: calls returning jax array values
_JAX_VALUE_PREFIXES = ("jnp.", "jax.numpy.", "jax.random.", "jax.lax.")

# the obs registry's mutation surface (Counter.inc / Histogram.observe
# / Gauge.set); `.at[...].set(...)` is jax's functional update, exempt
_METRIC_MUTATION_ATTRS = ("inc", "observe", "set")

# wall-clock reads that constant-fold under tracing: the time-module
# attribute forms plus their bare from-import names
_WALLCLOCK_SUFFIXES = (
    "time.time", "time.perf_counter", "time.monotonic",
    "time.time_ns", "time.perf_counter_ns", "time.monotonic_ns",
)
_WALLCLOCK_BARE = ("perf_counter", "monotonic", "perf_counter_ns",
                   "monotonic_ns", "time_ns")

# a call to any of these makes its function-valued args jit scopes;
# matched on the DOTTED SUFFIX of the callee (jax.lax.scan == lax.scan)
_JIT_WRAPPER_SUFFIXES = (
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.lax.scan", "lax.scan",
    "jax.lax.while_loop", "lax.while_loop", "jax.lax.cond", "lax.cond",
    "jax.lax.fori_loop", "lax.fori_loop", "jax.lax.switch", "lax.switch",
    "jax.lax.map", "lax.map", "shard_map", "jax.lax.associative_scan",
    "lax.associative_scan",
)

_JIT_DECORATOR_SUFFIXES = (
    "jax.jit", "jit.to_static", "to_static", "jax.checkpoint",
    "jax.remat", "jax.vmap", "jax.pmap",
)

_HOST_SYNC_ATTRS = ("numpy", "item", "tolist")
_NEUTRAL_ATTRS = ("shape", "ndim", "dtype", "size", "name")
_NEUTRAL_CALLS = ("isinstance", "len", "getattr", "hasattr", "type",
                  "repr", "str", "id")


class LintViolation:
    __slots__ = ("path", "rule", "qualname", "lineno", "message")

    def __init__(self, path, rule, qualname, lineno, message):
        self.path = path
        self.rule = rule
        self.qualname = qualname
        self.lineno = lineno
        self.message = message

    @property
    def key(self):
        """The allowlist key: stable across line-number drift."""
        return f"{self.path}:{self.rule}:{self.qualname}"

    def __repr__(self):
        return (f"{self.path}:{self.lineno}: {self.rule} "
                f"[{self.qualname}] {self.message}")


def _dotted(node):
    """'a.b.c' for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _suffix_match(dotted, suffixes):
    if dotted is None:
        return False
    return any(dotted == s or dotted.endswith("." + s) for s in suffixes)


class _FunctionInfo:
    def __init__(self, node, qualname, parent):
        self.node = node
        self.qualname = qualname
        self.parent = parent  # _FunctionInfo or None
        self.jit_entry = False  # directly decorated/wrapped

    def jit_scoped(self):
        info = self
        while info is not None:
            if info.jit_entry:
                return True
            info = info.parent
        return False


class _Collector(ast.NodeVisitor):
    """Pass 1: map every function/lambda to its qualname + lexical
    parent, and mark jit ENTRY functions (decorated, or referenced as a
    function argument of a jit wrapper call anywhere in the module)."""

    def __init__(self):
        self.functions = []  # [_FunctionInfo]
        self.by_node = {}
        self.by_name = {}  # bare name -> [info] (module-wide)
        self._stack = []

    def _add(self, node, name):
        parent = self._stack[-1] if self._stack else None
        qual = f"{parent.qualname}.{name}" if parent else name
        # class bodies: include class name for readability
        info = _FunctionInfo(node, qual, parent)
        self.functions.append(info)
        self.by_node[id(node)] = info
        self.by_name.setdefault(name, []).append(info)
        return info

    def visit_ClassDef(self, node):
        # classes don't form jit scopes and break the lexical-closure
        # chain: methods start a fresh function stack (their qualnames
        # are the method-level chain, without the class name)
        prev = self._stack
        self._stack = []
        for child in node.body:
            self.visit(child)
        self._stack = prev

    def _visit_fn(self, node, name):
        info = self._add(node, name)
        for dec in node.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            d = _dotted(target)
            if _suffix_match(d, _JIT_DECORATOR_SUFFIXES):
                info.jit_entry = True
            if isinstance(dec, ast.Call) and _dotted(dec.func) in (
                    "partial", "functools.partial") and dec.args:
                inner = _dotted(dec.args[0])
                if _suffix_match(inner, _JIT_DECORATOR_SUFFIXES):
                    info.jit_entry = True
        self._stack.append(info)
        self.generic_visit(node)
        self._stack.pop()

    def visit_FunctionDef(self, node):
        self._visit_fn(node, node.name)

    def visit_AsyncFunctionDef(self, node):
        self._visit_fn(node, node.name)

    def visit_Lambda(self, node):
        info = self._add(node, "<lambda>")
        self._stack.append(info)
        self.generic_visit(node)
        self._stack.pop()

    def visit_Call(self, node):
        callee = _dotted(node.func)
        if _suffix_match(callee, _JIT_WRAPPER_SUFFIXES):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, (ast.Lambda,)):
                    # visited later; mark after collection via node id
                    self._pending_lambda_entries = getattr(
                        self, "_pending_lambda_entries", set())
                    self._pending_lambda_entries.add(id(arg))
                elif isinstance(arg, ast.Name):
                    self._pending_name_entries = getattr(
                        self, "_pending_name_entries", set())
                    self._pending_name_entries.add(arg.id)
        self.generic_visit(node)

    def finalize(self):
        for lam_id in getattr(self, "_pending_lambda_entries", ()):
            info = self.by_node.get(lam_id)
            if info is not None:
                info.jit_entry = True
        for name in getattr(self, "_pending_name_entries", ()):
            for info in self.by_name.get(name, ()):
                info.jit_entry = True


def _mutable_default_violations(path, collector):
    out = []
    for info in collector.functions:
        node = info.node
        args = node.args
        defaults = list(args.defaults) + list(args.kw_defaults)
        for d in defaults:
            if d is None:
                continue
            bad = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call)
                and _dotted(d.func) in ("list", "dict", "set")
                and not d.args and not d.keywords)
            if bad:
                out.append(LintViolation(
                    path, "H105", info.qualname, d.lineno,
                    RULES["H105"]))
    return out


class _TaintChecker:
    """Pass 2: per jit-scoped function, run the local taint dataflow and
    emit H101-H104."""

    def __init__(self, path, info, inherited_taint=()):
        self.path = path
        self.info = info
        self.taint = set(inherited_taint)
        self.violations = []
        node = info.node
        a = node.args
        for arg in (list(a.posonlyargs) + list(a.args)
                    + list(a.kwonlyargs)
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            if arg.arg not in ("self", "cls"):
                self.taint.add(arg.arg)

    def _flag(self, rule, node, detail=""):
        msg = RULES[rule] + (f": {detail}" if detail else "")
        self.violations.append(LintViolation(
            self.path, rule, self.info.qualname, node.lineno, msg))

    # -- taint expression test ------------------------------------------
    def tainted(self, node):
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.taint
        if isinstance(node, ast.Attribute):
            if node.attr in _NEUTRAL_ATTRS:
                return False
            return self.tainted(node.value)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` are static decisions
            if all(isinstance(c, ast.Constant) and c.value is None
                   for c in node.comparators):
                return False
            return self.tainted(node.left) or any(
                self.tainted(c) for c in node.comparators)
        if isinstance(node, ast.BoolOp):
            return any(self.tainted(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return self.tainted(node.operand)
        if isinstance(node, ast.BinOp):
            return self.tainted(node.left) or self.tainted(node.right)
        if isinstance(node, ast.Call):
            callee = _dotted(node.func)
            if callee in _NEUTRAL_CALLS:
                return False
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _NEUTRAL_ATTRS:
                return False
            return any(self.tainted(a) for a in node.args) or any(
                self.tainted(kw.value) for kw in node.keywords) or (
                self.tainted(node.func)
                if isinstance(node.func, ast.Attribute) else False)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.tainted(e) for e in node.elts)
        if isinstance(node, ast.Subscript):
            return self.tainted(node.value)
        if isinstance(node, ast.IfExp):
            return (self.tainted(node.body) or self.tainted(node.orelse)
                    or self.tainted(node.test))
        if isinstance(node, ast.Starred):
            return self.tainted(node.value)
        return False

    # -- statement walk --------------------------------------------------
    def run(self):
        self._walk(self.info.node.body
                   if not isinstance(self.info.node, ast.Lambda)
                   else [ast.Expr(self.info.node.body)])
        return self.violations

    def _assign_target(self, target, is_tainted):
        if isinstance(target, ast.Name):
            if is_tainted:
                self.taint.add(target.id)
            else:
                self.taint.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._assign_target(e, is_tainted)
        elif isinstance(target, ast.Starred):
            self._assign_target(target.value, is_tainted)
        # attribute/subscript stores don't track

    def _walk(self, body):
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt):
        if isinstance(stmt, ast.Assign):
            t = self.tainted(stmt.value)
            self._scan_expr(stmt.value)
            for target in stmt.targets:
                self._assign_target(target, t)
        elif isinstance(stmt, ast.AugAssign):
            self._scan_expr(stmt.value)
            if self.tainted(stmt.value):
                self._assign_target(stmt.target, True)
        elif isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._scan_expr(stmt.value)
                self._assign_target(stmt.target,
                                    self.tainted(stmt.value))
        elif isinstance(stmt, ast.If):
            if self.tainted(stmt.test):
                self._flag("H104", stmt,
                           f"if {ast.unparse(stmt.test)[:60]}")
            self._scan_expr(stmt.test)
            self._walk(stmt.body)
            self._walk(stmt.orelse)
        elif isinstance(stmt, ast.While):
            if self.tainted(stmt.test):
                self._flag("H104", stmt,
                           f"while {ast.unparse(stmt.test)[:60]}")
            self._scan_expr(stmt.test)
            self._walk(stmt.body)
            self._walk(stmt.orelse)
        elif isinstance(stmt, ast.For):
            self._scan_expr(stmt.iter)
            self._assign_target(stmt.target, self.tainted(stmt.iter))
            self._walk(stmt.body)
            self._walk(stmt.orelse)
        elif isinstance(stmt, (ast.Return, ast.Expr)):
            if stmt.value is not None:
                self._scan_expr(stmt.value)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._scan_expr(item.context_expr)
            self._walk(stmt.body)
        elif isinstance(stmt, ast.Try):
            self._walk(stmt.body)
            for h in stmt.handlers:
                self._walk(h.body)
            self._walk(stmt.orelse)
            self._walk(stmt.finalbody)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested function: traced closure — checked separately with
            # inherited taint by lint_source
            pass
        else:
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.expr):
                    self._scan_expr(child)

    def _scan_expr(self, expr):
        """Find H101/H102/H103 hazards anywhere in an expression."""
        for node in ast.walk(expr):
            if isinstance(node, (ast.Lambda, ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                continue
            if not isinstance(node, ast.Call):
                continue
            # H101: .numpy()/.item()/.tolist()
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _HOST_SYNC_ATTRS \
                    and not node.args and not node.keywords:
                base = _dotted(node.func.value)
                if base not in ("np", "numpy", "jnp", "jax.numpy"):
                    self._flag(
                        "H101", node,
                        f".{node.func.attr}() on "
                        f"{ast.unparse(node.func.value)[:40]}")
                continue
            # H107: obs metric mutation — host dict state frozen into
            # the trace (x.at[idx].set(v) is functional, not a metric)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _METRIC_MUTATION_ATTRS:
                recv = node.func.value
                at_update = (isinstance(recv, ast.Subscript)
                             and isinstance(recv.value, ast.Attribute)
                             and recv.value.attr == "at")
                if not at_update:
                    self._flag(
                        "H107", node,
                        f"{ast.unparse(node.func)[:50]}(...)")
                    continue
            callee = _dotted(node.func)
            # H106: wall-clock read — hazardous REGARDLESS of taint
            # (the clock needs no traced operand to constant-fold)
            if callee is not None and (
                    _suffix_match(callee, _WALLCLOCK_SUFFIXES)
                    or callee in _WALLCLOCK_BARE):
                self._flag("H106", node, f"{callee}()")
                continue
            # H102: float/int/bool on tainted
            if callee in ("float", "int", "bool") and node.args \
                    and self.tainted(node.args[0]):
                self._flag("H102", node,
                           f"{callee}({ast.unparse(node.args[0])[:40]})")
                continue
            # H103: np.* on tainted
            if callee is not None and (
                    callee.startswith("np.")
                    or callee.startswith("numpy.")):
                if any(self.tainted(a) for a in node.args) or any(
                        self.tainted(kw.value) for kw in node.keywords):
                    self._flag("H103", node, f"{callee}(...)")


def _bench_exempt(path):
    """True for measurement/test code where explicit device syncs are
    the point: a ``tests`` path segment, or a ``bench*`` / ``test*`` /
    ``conftest*`` basename."""
    parts = path.replace(os.sep, "/").split("/")
    base = parts[-1]
    return ("tests" in parts[:-1]
            or base.startswith(("bench", "test", "conftest")))


class _HostEscapeChecker(_TaintChecker):
    """Pass 3 (H108/H109): HOST-side (non-jit) functions, where the
    hazard inverts — a jax array value coerced to a Python scalar or a
    numpy array is an implicit blocking device->host transfer. Taint
    seeds are DIRECT jax values (jnp./jax.numpy/jax.random/jax.lax
    call results and ``jax.device_put``), not the function's
    parameters — and deliberately NOT ``._value`` reads: the eager
    Tensor wrapper's contract IS host semantics, and its conversion
    points (``Tensor.numpy()``/``.item()``) are the audited, explicit
    sync surface. These rules exist to catch NEW jnp-direct escapes
    in runtime code, not to re-litigate the eager API."""

    def __init__(self, path, info):
        super().__init__(path, info, inherited_taint=())
        self.taint.clear()  # params are host values here, not tracers

    def _flag(self, rule, node, detail=""):
        # the inherited statement walk would also emit the jit-scope
        # rules (H104 on `if jax_value:` etc.); in host code those are
        # legal — only the escape rules belong to this pass
        if rule in ("H108", "H109"):
            super()._flag(rule, node, detail)

    def tainted(self, node):
        if isinstance(node, ast.Call):
            callee = _dotted(node.func)
            if callee is not None and (
                    callee.startswith(_JAX_VALUE_PREFIXES)
                    or callee == "jax.device_put"):
                return True
        return super().tainted(node)

    def _scan_expr(self, expr):
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            # H108a: bare .item() — on anything but an explicit numpy
            # receiver it is a device round-trip (jax arrays and the
            # eager Tensor wrapper both sync here)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" \
                    and not node.args and not node.keywords:
                base = _dotted(node.func.value)
                if base not in ("np", "numpy"):
                    self._flag(
                        "H108", node,
                        f".item() on "
                        f"{ast.unparse(node.func.value)[:40]}")
                continue
            callee = _dotted(node.func)
            # H108b: scalar coercion of a jax value
            if callee in ("float", "int", "bool") and node.args \
                    and self.tainted(node.args[0]):
                self._flag(
                    "H108", node,
                    f"{callee}({ast.unparse(node.args[0])[:40]})")
                continue
            # H109: numpy conversion of a jax value (the conversion
            # entry points only — np.testing asserts etc. sync too,
            # but the conversions are the ones that hide in runtime
            # code paths behind an innocent-looking cast)
            if callee in ("np.asarray", "np.array", "numpy.asarray",
                          "numpy.array", "np.ascontiguousarray",
                          "numpy.ascontiguousarray", "np.copy",
                          "numpy.copy"):
                if any(self.tainted(a) for a in node.args) or any(
                        self.tainted(kw.value)
                        for kw in node.keywords):
                    self._flag("H109", node, f"{callee}(...)")


def _block_until_ready_violations(path, tree, collector):
    """H110: any block_until_ready call in a non-bench/test file —
    jit scope or host, the barrier does not belong in library code."""
    if _bench_exempt(path):
        return []
    out = []

    def visit(node, qual):
        if not isinstance(node, ast.Call):
            return
        hit = (isinstance(node.func, ast.Attribute)
               and node.func.attr == "block_until_ready")
        if not hit:
            callee = _dotted(node.func)
            hit = callee is not None and _suffix_match(
                callee, ("jax.block_until_ready",))
        if hit:
            out.append(LintViolation(
                path, "H110", qual, node.lineno,
                RULES["H110"]
                + f": {ast.unparse(node.func)[:50]}(...)"))

    def walk(node, qual):
        for child in ast.iter_child_nodes(node):
            info = collector.by_node.get(id(child))
            child_qual = info.qualname if info is not None else qual
            visit(child, child_qual)
            walk(child, child_qual)

    walk(tree, "<module>")
    return out


def lint_source(source, path="<string>"):
    """Lint one module's source text; returns [LintViolation]."""
    tree = ast.parse(source, filename=path)
    collector = _Collector()
    collector.visit(tree)
    collector.finalize()

    violations = _mutable_default_violations(path, collector)
    violations.extend(_block_until_ready_violations(
        path, tree, collector))

    for info in collector.functions:
        if not info.jit_scoped():
            checker = _HostEscapeChecker(path, info)
            violations.extend(checker.run())
            continue
        inherited = set()
        parent = info.parent
        while parent is not None:
            # closure variables of enclosing jit scopes are traced too;
            # approximate with the enclosing params
            a = parent.node.args
            for arg in list(a.posonlyargs) + list(a.args) \
                    + list(a.kwonlyargs):
                if arg.arg not in ("self", "cls"):
                    inherited.add(arg.arg)
            parent = parent.parent
        checker = _TaintChecker(path, info, inherited)
        violations.extend(checker.run())
    return violations


def load_allowlist(path):
    """Parse an allowlist file: one ``path:RULE:qualname  # reason``
    per line; the justification comment is REQUIRED. Returns
    dict key -> reason. Raises ValueError on an unjustified entry."""
    entries = {}
    with open(path) as f:
        for ln_no, raw in enumerate(f, 1):
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            if "#" not in line:
                raise ValueError(
                    f"{path}:{ln_no}: allowlist entry lacks the "
                    f"required '# <justification>' comment: {line!r}")
            key, reason = line.split("#", 1)
            key = key.strip()
            reason = reason.strip()
            if not reason:
                raise ValueError(
                    f"{path}:{ln_no}: empty justification for {key!r}")
            entries[key] = reason
    return entries


def _iter_py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def lint_paths(paths, allowlist=None, root=None):
    """Lint every .py file under ``paths``. ``allowlist`` maps
    ``relpath:RULE:qualname`` keys to justifications; matches are
    suppressed. Returns (violations, unused_allowlist_keys) — stale
    allowlist entries are surfaced so the list cannot rot."""
    allowlist = dict(allowlist or {})
    root = root or os.getcwd()
    violations = []
    used = set()
    for fp in _iter_py_files(paths):
        rel = os.path.relpath(fp, root).replace(os.sep, "/")
        with open(fp, encoding="utf-8") as f:
            src = f.read()
        try:
            file_violations = lint_source(src, rel)
        except SyntaxError as e:
            violations.append(LintViolation(
                rel, "H100", "<module>", e.lineno or 0,
                f"syntax error: {e.msg}"))
            continue
        for v in file_violations:
            if v.key in allowlist:
                used.add(v.key)
                continue
            violations.append(v)
    unused = sorted(set(allowlist) - used)
    return violations, unused


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis.lint",
        description="tracer-hazard linter (see module docstring)")
    ap.add_argument("paths", nargs="+")
    ap.add_argument("--allowlist", default=None,
                    help="allowlist file (default: the checked-in "
                         "paddle_tpu/analysis/lint_allowlist.txt)")
    ap.add_argument("--no-default-allowlist", action="store_true")
    ap.add_argument("--strict-allowlist", action="store_true",
                    help="(default) fail on stale allowlist entries")
    ap.add_argument("--allow-stale", action="store_true",
                    help="tolerate stale (unused) allowlist entries; "
                         "by default they fail the lint so the "
                         "allowlist can only shrink")
    args = ap.parse_args(argv)

    allow = {}
    if args.allowlist:
        allow = load_allowlist(args.allowlist)
    elif not args.no_default_allowlist \
            and os.path.exists(DEFAULT_ALLOWLIST):
        allow = load_allowlist(DEFAULT_ALLOWLIST)

    violations, unused = lint_paths(args.paths, allow)
    for v in violations:
        print(v)
    if unused:
        print(f"{'note' if args.allow_stale else 'error'}: "
              f"{len(unused)} stale allowlist entr"
              f"{'y' if len(unused) == 1 else 'ies'} (allowlisted "
              f"hazard no longer exists — delete the line): "
              + ", ".join(unused), file=sys.stderr)
    if violations or (unused and not args.allow_stale):
        print(f"{len(violations)} tracer hazard(s) found",
              file=sys.stderr)
        return 1
    print(f"clean: 0 tracer hazards "
          f"({len(allow)} allowlisted exception(s))", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
