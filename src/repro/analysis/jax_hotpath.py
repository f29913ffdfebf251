"""Host-sync and trace hazards on JAX hot paths.

The serving contract since PR 3 is *one* host sync per batcher tick; jitted
step functions must stay on device. This checker tracks device provenance
through a function body (values produced by ``jnp.*``/``jax.*`` calls,
engine step methods, or class attributes assigned device values anywhere in
the class) and flags operations that force a device->host transfer or a
retrace where they hurt:

  hot scopes
    * functions decorated ``@jax.jit`` (also via ``functools.partial``) —
      every parameter is a tracer there;
    * any method of a class whose name contains ``Batcher`` (tick loops);
    * the body of any ``for``/``while`` loop elsewhere (per-iteration sync).

  rules
    jax-host-sync      np.asarray/np.array/int()/float()/bool()/.item()/
                       .tolist() applied to a traced value in a hot scope
    jax-traced-branch  Python ``if``/``while``/ternary/``assert`` on a
                       traced value, or iterating one, in a hot scope
    jax-recompile      inside @jax.jit: numpy ops on tracers or python
                       slicing with traced bounds (shape becomes dynamic)

``np.asarray(x)`` yields a *host* value: subsequent ``int(toks[i])`` is
clean. Intentional syncs (the batcher's single per-tick transfer, EOS
checks) are marked ``# repro-lint: allow[jax-host-sync]`` at the call site.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from repro.analysis.findings import ERROR, WARNING, RawFinding
from repro.analysis.framework import (ParsedModule, decorator_names,
                                      dotted_name, root_name)

#: methods whose results live on device (engine/model step functions)
_PRODUCER_METHODS = {
    "prefill", "decode", "decode_paged", "prefill_chunk", "generate_step",
    "_prefill", "_decode", "_decode_paged", "_prefill_chunk", "_select",
    "commit_paged", "_commit",
    "new_cache", "new_paged_cache", "init_cache", "init_paged_cache",
    "apply", "sample",
}

_SYNC_BUILTINS = {"int", "float", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist", "__array__"}
_NP_MODULES = {"np", "numpy", "onp"}
_JAX_MODULES = {"jnp", "jax", "lax"}
#: attribute reads that are static metadata, not device data
_STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "sharding"}
#: calls that return host/static values even on traced args
_HOST_RESULT_CALLS = {"len", "range", "isinstance", "getattr", "type", "id",
                      "repr", "str"}


_JIT_NAMES = ("jax.jit", "jit", "pjit", "jax.pjit")


def _is_jit_decorated(fn) -> bool:
    names = decorator_names(fn)
    return any(n in _JIT_NAMES for n in names)


def _jit_static_params(fn) -> Set[str]:
    """Parameter names marked static via static_argnames/static_argnums in a
    ``@jax.jit``/``functools.partial(jax.jit, ...)`` decorator — these are
    Python values, not tracers."""
    static: Set[str] = set()
    a = fn.args
    positional = [p.arg for p in (a.posonlyargs + a.args)]
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        involved = [dotted_name(dec.func)] + \
            [dotted_name(x) for x in dec.args]
        if not any(n in _JIT_NAMES for n in involved if n):
            continue
        for kw in dec.keywords:
            if kw.arg == "static_argnames":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, str):
                        static.add(c.value)
            elif kw.arg == "static_argnums":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and isinstance(c.value, int):
                        if 0 <= c.value < len(positional):
                            static.add(positional[c.value])
    return static


def _is_jaxish_call(node: ast.Call) -> bool:
    callee = dotted_name(node.func)
    if callee:
        head = callee.split(".", 1)[0]
        if head in _JAX_MODULES:
            return callee not in ("jax.jit", "jax.block_until_ready")
    if isinstance(node.func, ast.Attribute) \
            and node.func.attr in _PRODUCER_METHODS:
        return True
    return False


class JaxHotPathChecker:
    name = "jax-hot-path"
    rules = {
        "jax-host-sync": "device->host transfer on a JAX hot path",
        "jax-traced-branch": "Python control flow on a traced/device value",
        "jax-recompile": "recompile/host-fallback hazard inside @jax.jit",
    }

    def check(self, module: ParsedModule) -> Iterable[RawFinding]:
        out: List[RawFinding] = []
        for node in module.tree.body:
            self._walk_toplevel(node, out, class_ctx=None)
        return out

    def _walk_toplevel(self, node, out, class_ctx) -> None:
        if isinstance(node, ast.ClassDef):
            traced_attrs = _class_traced_attrs(node)
            hot_class = "Batcher" in node.name or "Engine" in node.name
            for sub in node.body:
                self._walk_toplevel(sub, out,
                                    class_ctx=(hot_class, traced_attrs))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            hot_class, traced_attrs = class_ctx or (False, frozenset())
            out.extend(_FunctionScan(node, jit=_is_jit_decorated(node),
                                     hot_method=hot_class,
                                     traced_attrs=traced_attrs).run())
            for sub in ast.walk(node):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and sub is not node:
                    out.extend(_FunctionScan(
                        sub, jit=_is_jit_decorated(sub),
                        hot_method=hot_class,
                        traced_attrs=traced_attrs).run())


def _class_traced_attrs(cls: ast.ClassDef) -> frozenset:
    """Attributes assigned device values anywhere in the class body
    (``self.cache = jnp.zeros(...)`` in __init__ makes ``self.cache``
    traced in every method)."""
    attrs: Set[str] = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign):
            if _seed_traced_expr(node.value, attrs):
                flat = []
                for t in node.targets:
                    flat.extend(t.elts if isinstance(t, (ast.Tuple, ast.List))
                                else [t])
                for t in flat:
                    if isinstance(t, ast.Attribute) \
                            and isinstance(t.value, ast.Name) \
                            and t.value.id == "self":
                        attrs.add(t.attr)
    return frozenset(attrs)


def _seed_traced_expr(node, attrs: Set[str]) -> bool:
    """Conservative 'is this expression device-valued' for attr seeding."""
    if isinstance(node, ast.Call):
        if _is_jaxish_call(node):
            return True
        callee = dotted_name(node.func)
        if callee in ("dict",) or (callee and callee.startswith("dict")):
            return any(_seed_traced_expr(kw.value, attrs)
                       for kw in node.keywords)
        return False
    if isinstance(node, (ast.Subscript, ast.Attribute)):
        return _seed_traced_expr(node.value, attrs)
    if isinstance(node, ast.Name):
        return False
    if isinstance(node, ast.Attribute):
        return node.attr in attrs
    return False


class _FunctionScan:
    def __init__(self, fn, *, jit: bool, hot_method: bool,
                 traced_attrs: frozenset):
        self.fn = fn
        self.jit = jit
        self.hot_method = hot_method
        self.traced_attrs = traced_attrs
        self.loop_depth = 0
        self.findings: List[RawFinding] = []
        self.traced: Set[str] = set()
        if jit:
            static = _jit_static_params(fn)
            a = fn.args
            for p in (a.posonlyargs + a.args + a.kwonlyargs):
                if p.arg not in ("self", "cls") and p.arg not in static:
                    self.traced.add(p.arg)

    # hot = a per-iteration context where a sync is a per-tick cost
    @property
    def hot(self) -> bool:
        return self.jit or self.hot_method or self.loop_depth > 0

    def run(self) -> List[RawFinding]:
        for stmt in self.fn.body:
            self.stmt(stmt)
        return self.findings

    def report(self, node, rule, severity, message):
        self.findings.append(RawFinding(node, rule, severity, message))

    # -------------------------------------------------------------- tracking
    def is_traced(self, node) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.traced
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            if isinstance(node.value, ast.Name) and node.value.id == "self":
                return node.attr in self.traced_attrs
            return self.is_traced(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_traced(node.value)
        if isinstance(node, ast.Call):
            return self.call_traced(node)
        if isinstance(node, ast.BinOp):
            return self.is_traced(node.left) or self.is_traced(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_traced(node.operand)
        if isinstance(node, ast.Compare):
            return self.is_traced(node.left) \
                or any(self.is_traced(c) for c in node.comparators)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_traced(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return self.is_traced(node.body) or self.is_traced(node.orelse)
        return False

    def call_traced(self, node: ast.Call) -> bool:
        callee = dotted_name(node.func)
        if callee:
            head = callee.split(".", 1)[0]
            leaf = callee.rsplit(".", 1)[-1]
            if callee in _HOST_RESULT_CALLS or leaf in _SYNC_METHODS \
                    or callee in _SYNC_BUILTINS:
                return False            # result lands on host
            if head in _NP_MODULES:
                return False            # numpy result is host-side
        if _is_jaxish_call(node):
            return True
        # method call on a traced receiver (.astype, .at[i].set, ...)
        if isinstance(node.func, ast.Attribute) \
                and self.is_traced(node.func.value):
            return True
        # calling a traced callable (self._prefill = jax.jit(...))
        if self.is_traced(node.func) and not isinstance(node.func,
                                                        ast.Attribute):
            return True
        # plain constructors propagate (dict(cache, k=traced), tuple, ...)
        if callee in ("dict", "tuple", "list"):
            return any(self.is_traced(a) for a in node.args) \
                or any(self.is_traced(k.value) for k in node.keywords)
        return False

    def mark(self, target, traced: bool) -> None:
        if isinstance(target, ast.Name):
            if traced:
                self.traced.add(target.id)
            else:
                self.traced.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self.mark(e, traced)
        elif isinstance(target, ast.Starred):
            self.mark(target.value, traced)

    # ------------------------------------------------------------ statements
    def stmt(self, s) -> None:
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            return                      # scanned separately
        if isinstance(s, ast.Assign):
            self.expr(s.value)
            t = self.is_traced(s.value)
            for target in s.targets:
                self.mark(target, t)
            return
        if isinstance(s, (ast.AnnAssign, ast.AugAssign)):
            if getattr(s, "value", None) is not None:
                self.expr(s.value)
                if isinstance(s.target, ast.Name):
                    if isinstance(s, ast.AugAssign):
                        if self.is_traced(s.value):
                            self.traced.add(s.target.id)
                    else:
                        self.mark(s.target, self.is_traced(s.value))
            return
        if isinstance(s, (ast.If, ast.While)):
            self.expr(s.test)
            # a while-test re-evaluates every iteration: hot by definition
            if (self.hot or isinstance(s, ast.While)) \
                    and self.is_traced(s.test) \
                    and not _is_sync_call(s.test):
                kind = "if" if isinstance(s, ast.If) else "while"
                self.report(s, "jax-traced-branch", ERROR,
                            f"`{kind}` on a traced value forces a host sync "
                            f"per evaluation; use jnp.where/lax.cond or sync "
                            f"once outside the loop")
            if isinstance(s, ast.While):
                self.loop_depth += 1
            for b in s.body + s.orelse:
                self.stmt(b)
            if isinstance(s, ast.While):
                self.loop_depth -= 1
            return
        if isinstance(s, ast.For):
            self.expr(s.iter)
            if self.hot and self.is_traced(s.iter):
                self.report(s, "jax-traced-branch", ERROR,
                            "Python iteration over a traced value transfers "
                            "one element per step; transfer once with "
                            "np.asarray and iterate the host copy")
            self.mark(s.target, False)
            self.loop_depth += 1
            for b in s.body + s.orelse:
                self.stmt(b)
            self.loop_depth -= 1
            return
        if isinstance(s, ast.Return):
            if s.value is not None:
                self.expr(s.value)
            return
        if isinstance(s, ast.Expr):
            self.expr(s.value)
            return
        if isinstance(s, ast.With):
            for item in s.items:
                self.expr(item.context_expr)
            for b in s.body:
                self.stmt(b)
            return
        if isinstance(s, ast.Try):
            for b in s.body + s.orelse + s.finalbody:
                self.stmt(b)
            for h in s.handlers:
                for b in h.body:
                    self.stmt(b)
            return
        if isinstance(s, ast.Assert):
            self.expr(s.test)
            if self.hot and self.is_traced(s.test):
                self.report(s, "jax-traced-branch", ERROR,
                            "assert on a traced value syncs the device; use "
                            "checkify or debug.print, or assert on shapes")
            return

    # ----------------------------------------------------------- expressions
    def expr(self, node) -> None:
        if node is None:
            return
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self.check_call(sub)
            elif isinstance(sub, ast.IfExp) and self.hot \
                    and self.is_traced(sub.test):
                self.report(sub, "jax-traced-branch", ERROR,
                            "ternary on a traced value forces a host sync")
            elif isinstance(sub, ast.Subscript) and self.jit \
                    and isinstance(sub.slice, ast.Slice):
                bounds = [b for b in (sub.slice.lower, sub.slice.upper,
                                      sub.slice.step) if b is not None]
                if any(self.is_traced(b) for b in bounds):
                    self.report(sub, "jax-recompile", WARNING,
                                "slice bounds depend on a traced value: "
                                "dynamic shapes retrace or fail under jit; "
                                "use lax.dynamic_slice")

    def check_call(self, node: ast.Call) -> None:
        callee = dotted_name(node.func)
        if not callee:
            return
        head = callee.split(".", 1)[0]
        leaf = callee.rsplit(".", 1)[-1]
        args_traced = any(self.is_traced(a) for a in node.args)
        if callee in _SYNC_BUILTINS and len(node.args) == 1 and args_traced:
            if self.hot:
                self.report(node, "jax-host-sync", ERROR,
                            f"{callee}() on a traced value blocks on the "
                            f"device in a hot scope")
            return
        if leaf in _SYNC_METHODS and isinstance(node.func, ast.Attribute) \
                and self.is_traced(node.func.value):
            if self.hot:
                self.report(node, "jax-host-sync", ERROR,
                            f".{leaf}() on a traced value blocks on the "
                            f"device in a hot scope")
            return
        if head in _NP_MODULES and args_traced:
            if self.jit:
                self.report(node, "jax-recompile", WARNING,
                            f"numpy op {callee}() on a tracer inside @jax.jit"
                            f" constant-folds or fails; use jnp.{leaf}")
            elif self.hot:
                self.report(node, "jax-host-sync", ERROR,
                            f"{callee}() transfers a device value to host in "
                            f"a hot scope")
            return


def _is_sync_call(node) -> bool:
    """`if bool(x):` is already reported at the bool() call."""
    return isinstance(node, ast.Call)
