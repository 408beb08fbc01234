"""Retrace-hazard pass: the silent-recompile and trace-break lint.

The plan cache (`repro.exec.plan`) audits retraces at *runtime* -- a flat
miss counter proves a serving loop is not recompiling.  This pass moves the
three statically-detectable hazard classes to lint time:

RT001  traced-branch (error)
       Python-level `if`/`while`/`assert`/ternary on a traced value inside
       a traced scope.  Traced scopes are functions decorated with
       `jax.jit`/`partial(jax.jit, ...)` AND -- the `exec/stages.py`
       convention -- any function with a `jax.Array`-annotated parameter:
       the annotation is the purity contract, so branching on such a value
       is a concretization (ConcretizationTypeError at best, a silent
       per-value retrace at worst).  Shape/dtype access (`x.shape`,
       `x.ndim`, `len(x)`) and `is None` tests are static and exempt.

RT002  tracer-concretize (error)
       `float()`/`int()`/`bool()`/`.item()`/`np.asarray()`/`np.array()`
       applied to a traced value inside a traced scope: forces a device
       sync and breaks the trace.

RT003  unhashable-static-arg (error)
       A call site of a module-level jitted function passing a mutable
       literal (list/dict/set/comprehension) in a `static_argnames`
       position: static args key the jit cache, so they must be hashable --
       this raises at call time on current jax and silently retraces per
       call under older dispatch paths.

RT004  mutable-trace-config (warning)
       `jax.jit`/`pl.pallas_call`/`shard_map` called with a mutable literal
       for a cache-keying config kwarg (`static_argnames`, `grid`, ...):
       accepted by jax today, but aliasable -- a later in-place mutation
       changes the trace key out from under the cache.

Traced-value propagation is a simple forward walk: parameters annotated
`jax.Array` seed the set; assignment from an expression that *consumes* a
traced value taints the targets; `.shape`-style static projections sanitize.
No control-flow join is attempted -- straight-line taint is what the stage
idiom needs.
"""
from __future__ import annotations

import ast
from typing import Iterator

from .common import (ERROR, MUTABLE_LITERALS, WARNING, Finding, SourceFile,
                     annotation_name)

ARRAY_ANNOTATIONS = {"jax.Array", "jnp.ndarray", "jax.numpy.ndarray"}
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding"}
STATIC_CALLS = {"len", "isinstance", "type", "hasattr", "getattr"}
CONCRETIZING_CALLS = {"float", "int", "bool", "complex"}
NUMPY_CONCRETIZERS = {"numpy.asarray", "numpy.array", "numpy.float32",
                      "numpy.float64", "numpy.int32", "numpy.int64"}
JIT_NAMES = {"jax.jit", "jax.pmap"}
TRACE_WRAPPERS = {"jax.jit", "jax.pmap", "jax.experimental.pallas.pallas_call",
                  "jax.shard_map"}
# kwargs of the trace wrappers that key a trace cache (or pin kernel
# structure) and therefore must not alias mutable state
TRACE_CONFIG_KWARGS = {"static_argnums", "static_argnames", "donate_argnums",
                       "donate_argnames", "grid", "axis_names"}


def _jit_decoration(node: ast.FunctionDef | ast.AsyncFunctionDef,
                    sf: SourceFile) -> tuple[bool, set[str]]:
    """(is_jit_decorated, static param names).  Static args are Python
    values at trace time, not tracers -- branching on them is fine."""
    for dec in node.decorator_list:
        call = None
        if isinstance(dec, ast.Call):
            callee = sf.resolve(dec.func)
            if callee in JIT_NAMES:
                call = dec
            elif (callee in ("functools.partial", "partial") and dec.args
                    and sf.resolve(dec.args[0]) in JIT_NAMES):
                call = dec
        elif sf.resolve(dec) in JIT_NAMES:
            return True, set()
        if call is None:
            continue
        static: set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                if isinstance(kw.value, (ast.Tuple, ast.List)):
                    static |= {e.value for e in kw.value.elts
                               if isinstance(e, ast.Constant)}
                elif (isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, str)):
                    static.add(kw.value.value)
        return True, static
    return False, set()


def _array_params(node: ast.FunctionDef | ast.AsyncFunctionDef,
                  sf: SourceFile) -> set[str]:
    args = node.args
    every = (args.posonlyargs + args.args + args.kwonlyargs
             + ([args.vararg] if args.vararg else [])
             + ([args.kwarg] if args.kwarg else []))
    return {
        a.arg for a in every
        if annotation_name(a.annotation, sf) in ARRAY_ANNOTATIONS
    }


def _consumes_traced(expr: ast.AST, traced: set[str],
                     sf: SourceFile) -> bool:
    """True when evaluating `expr` consumes a traced *value* (static
    projections -- .shape, len(), is-None tests -- do not count)."""
    if isinstance(expr, ast.Name):
        return expr.id in traced
    if isinstance(expr, ast.Attribute):
        if expr.attr in STATIC_ATTRS:
            return False
        return _consumes_traced(expr.value, traced, sf)
    if isinstance(expr, ast.Call):
        fname = sf.resolve(expr.func)
        if fname in STATIC_CALLS:
            return False
        args = list(expr.args) + [kw.value for kw in expr.keywords]
        if isinstance(expr.func, ast.Attribute):
            args.append(expr.func.value)
        return any(_consumes_traced(a, traced, sf) for a in args)
    if isinstance(expr, ast.Compare):
        # `x is None` / `x is not None` are static plan-shape switches
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in expr.ops):
            return False
        return any(_consumes_traced(e, traced, sf)
                   for e in [expr.left] + expr.comparators)
    if isinstance(expr, ast.Starred):
        return _consumes_traced(expr.value, traced, sf)
    if isinstance(expr, (ast.BinOp, ast.BoolOp, ast.UnaryOp, ast.Subscript,
                         ast.IfExp, ast.Tuple, ast.List, ast.Set)):
        return any(_consumes_traced(c, traced, sf)
                   for c in ast.iter_child_nodes(expr)
                   if isinstance(c, ast.expr))
    return False


class _TracedScope(ast.NodeVisitor):
    """Walk one traced function: propagate taint, flag branches and
    concretizations."""

    def __init__(self, sf: SourceFile, traced: set[str]):
        self.sf = sf
        self.traced = set(traced)
        self.findings: list[Finding] = []

    # -- taint propagation ---------------------------------------------------

    def _taint_targets(self, targets: list[ast.expr], tainted: bool) -> None:
        for t in targets:
            if isinstance(t, ast.Name):
                if tainted:
                    self.traced.add(t.id)
                else:
                    self.traced.discard(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                self._taint_targets(list(t.elts), tainted)

    def visit_Assign(self, node: ast.Assign) -> None:
        self.generic_visit(node)
        self._taint_targets(node.targets,
                            _consumes_traced(node.value, self.traced, self.sf))

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        self.generic_visit(node)
        if node.value is not None:
            self._taint_targets(
                [node.target],
                _consumes_traced(node.value, self.traced, self.sf))

    # -- RT001: python branches on traced values ----------------------------

    def _flag_branch(self, test: ast.expr, what: str) -> None:
        if _consumes_traced(test, self.traced, self.sf):
            self.findings.append(self.sf.finding(
                "RT001", ERROR, test,
                f"Python-level {what} on a traced value inside a traced "
                "scope: concretizes the tracer (use jnp.where / lax.cond, "
                "or hoist the decision to plan-resolution time)",
            ))

    def visit_If(self, node: ast.If) -> None:
        self._flag_branch(node.test, "`if`")
        self.generic_visit(node)

    def visit_While(self, node: ast.While) -> None:
        self._flag_branch(node.test, "`while`")
        self.generic_visit(node)

    def visit_IfExp(self, node: ast.IfExp) -> None:
        self._flag_branch(node.test, "conditional expression")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert) -> None:
        self._flag_branch(node.test, "`assert`")
        self.generic_visit(node)

    # -- RT002: concretizing calls ------------------------------------------

    def visit_Call(self, node: ast.Call) -> None:
        fname = self.sf.resolve(node.func)
        if (fname in CONCRETIZING_CALLS and node.args
                and _consumes_traced(node.args[0], self.traced, self.sf)):
            self.findings.append(self.sf.finding(
                "RT002", ERROR, node,
                f"`{fname}()` of a traced value inside a traced scope: "
                "forces a host sync and breaks the trace",
            ))
        elif (fname in NUMPY_CONCRETIZERS and node.args
                and _consumes_traced(node.args[0], self.traced, self.sf)):
            self.findings.append(self.sf.finding(
                "RT002", ERROR, node,
                f"`{fname}()` of a traced value inside a traced scope: "
                "numpy materializes the tracer on host (use jnp)",
            ))
        elif (isinstance(node.func, ast.Attribute)
                and node.func.attr in ("item", "tolist")
                and _consumes_traced(node.func.value, self.traced, self.sf)):
            self.findings.append(self.sf.finding(
                "RT002", ERROR, node,
                f"`.{node.func.attr}()` on a traced value inside a traced "
                "scope: forces a host sync and breaks the trace",
            ))
        self.generic_visit(node)

    # nested defs start their own scope (closures over tracers are flagged
    # when the nested function itself carries the annotation/decorator)
    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]


def _jitted_static_names(sf: SourceFile) -> dict[str, tuple[list[str], int]]:
    """Module-level jitted defs with static_argnames: name ->
    (static names in order-independent list, total positional arity)."""
    out: dict[str, tuple[list[str], int]] = {}
    for node in ast.walk(sf.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            if not (isinstance(dec, ast.Call)
                    and sf.resolve(dec.func) in ("functools.partial", "partial")
                    and dec.args and sf.resolve(dec.args[0]) in JIT_NAMES):
                continue
            for kw in dec.keywords:
                if kw.arg == "static_argnames" and isinstance(
                        kw.value, (ast.Tuple, ast.List)):
                    names = [e.value for e in kw.value.elts
                             if isinstance(e, ast.Constant)]
                    arity = len(node.args.posonlyargs) + len(node.args.args)
                    out[node.name] = (names, arity)
    return out


def _check_static_call_sites(sf: SourceFile,
                             jitted: dict[str, tuple[list[str], int]],
                             findings: list[Finding]) -> None:
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        name = None
        if isinstance(node.func, ast.Name):
            name = node.func.id
        elif isinstance(node.func, ast.Attribute):
            name = node.func.attr
        if name not in jitted:
            continue
        static_names, _ = jitted[name]
        for kw in node.keywords:
            if kw.arg in static_names and isinstance(kw.value,
                                                     MUTABLE_LITERALS):
                findings.append(sf.finding(
                    "RT003", ERROR, kw.value,
                    f"mutable literal passed for static arg "
                    f"`{kw.arg}` of jitted `{name}`: static args key the "
                    "jit cache and must be hashable (use a tuple)",
                ))


def _check_trace_config(sf: SourceFile, findings: list[Finding]) -> None:
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        if sf.resolve(node.func) not in TRACE_WRAPPERS:
            continue
        for kw in node.keywords:
            if kw.arg in TRACE_CONFIG_KWARGS and isinstance(
                    kw.value, MUTABLE_LITERALS):
                findings.append(sf.finding(
                    "RT004", WARNING, kw.value,
                    f"mutable literal for trace-config kwarg `{kw.arg}` of "
                    f"`{sf.resolve(node.func)}`: aliasable state in a "
                    "cache key -- use a tuple",
                ))


def run(sources: list[SourceFile]) -> Iterator[Finding]:
    for sf in sources:
        jitted = _jitted_static_names(sf)
        findings: list[Finding] = []
        _check_static_call_sites(sf, jitted, findings)
        _check_trace_config(sf, findings)
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            traced = _array_params(node, sf)
            jitted_fn, static = _jit_decoration(node, sf)
            if jitted_fn:
                # under jit every non-static parameter is a tracer,
                # annotated or not
                args = node.args
                traced |= {a.arg for a in args.posonlyargs + args.args
                           + args.kwonlyargs} - static
            elif not traced:
                continue
            scope = _TracedScope(sf, traced)
            for stmt in node.body:
                scope.visit(stmt)
            findings.extend(scope.findings)
        yield from findings
