"""Best-effort intra-package call graph + ``jax.jit`` root discovery.

The recompile pass needs "which functions can execute *inside* a traced
program". Roots are functions handed to ``jax.jit`` (decorator, call, or
``functools.partial(jax.jit, ...)``); edges are direct calls, resolved
conservatively:

- ``f(...)``        -> a def named ``f`` in the same scope/module, or the
  import target when ``f`` was imported;
- ``mod.f(...)``    -> ``f`` in the module ``mod`` aliases;
- ``self.f(...)``   -> method ``f`` of the enclosing class.

Unresolvable names fall back to a bare-name match across the package
when the name is rare (<= ``_MAX_FALLBACK`` defs); common names
(``__init__``, ``apply``) are dropped rather than flooding the graph.
Framework indirection (``nn.Module.apply``, ``lax.scan`` bodies passed
as values) is *not* chased — the pass documents that direct calls are
the contract, and jit-root lambdas/closures are walked in place.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from machine_learning_apache_spark_tpu.analysis.core import Module

__all__ = ["CallGraph", "FuncInfo", "build_call_graph"]

_MAX_FALLBACK = 8

#: method names never resolved via the cross-class bare fallback: these
#: collide with builtin container / jax.Array methods (``x.at[i].set``,
#: ``dict.update``) and would drag host-side telemetry classes into the
#: jit-reachable set.
_ATTR_FALLBACK_DENY = {
    "set", "get", "update", "add", "append", "extend", "pop", "copy",
    "items", "keys", "values", "split", "join", "mean", "sum", "min",
    "max", "reshape", "astype", "apply", "write", "read", "close",
    "emit", "inc", "dec", "observe", "put", "index", "count",
}

#: decorator/call spellings that mean "this function is jitted"
_JIT_NAMES = {"jit"}
_JIT_ATTRS = {("jax", "jit")}


@dataclass
class FuncInfo:
    """One function/lambda definition in the package."""

    qual: str  # "pkg.mod.Class.name" / "pkg.mod.name" / "...<lambda:42>"
    module: Module
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    cls: str | None = None  # enclosing class bare name
    bare: str = ""
    #: local (nested) defs visible by bare name from inside this function
    locals_: dict[str, str] = field(default_factory=dict)


def _is_jit_expr(node: ast.AST) -> bool:
    """Is this expression ``jax.jit`` / ``jit``?"""
    if isinstance(node, ast.Attribute):
        base = node.value
        return (
            isinstance(base, ast.Name)
            and (base.id, node.attr) in _JIT_ATTRS
        )
    if isinstance(node, ast.Name):
        return node.id in _JIT_NAMES
    return False


def jit_application(node: ast.AST) -> ast.Call | None:
    """If ``node`` is a jit application — ``jax.jit(...)`` or
    ``functools.partial(jax.jit, ...)`` — return the Call carrying the
    jit kwargs (the partial/jit call itself)."""
    if not isinstance(node, ast.Call):
        return None
    if _is_jit_expr(node.func):
        return node
    # functools.partial(jax.jit, donate_argnums=0) / partial(jax.jit, ...)
    fn = node.func
    is_partial = (isinstance(fn, ast.Name) and fn.id == "partial") or (
        isinstance(fn, ast.Attribute)
        and fn.attr == "partial"
        and isinstance(fn.value, ast.Name)
        and fn.value.id == "functools"
    )
    if is_partial and node.args and _is_jit_expr(node.args[0]):
        return node
    return None


class _ModuleIndex(ast.NodeVisitor):
    """Defs + import aliases for one module."""

    def __init__(self, mod: Module, graph: "CallGraph"):
        self.mod = mod
        self.graph = graph
        self.scope: list[str] = []  # class/function name stack
        self.cls: list[str] = []

    # -- imports (collected at any scope) ------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            local = alias.asname or alias.name.split(".")[0]
            target = alias.name if alias.asname else alias.name.split(".")[0]
            self.graph.imports[self.mod.name][local] = target
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module and node.level == 0:
            for alias in node.names:
                local = alias.asname or alias.name
                self.graph.imports[self.mod.name][local] = (
                    f"{node.module}.{alias.name}"
                )
        self.generic_visit(node)

    # -- defs -----------------------------------------------------------------
    def _add_def(self, node, name: str) -> None:
        qual = ".".join([self.mod.name, *self.scope, name])
        info = FuncInfo(
            qual=qual, module=self.mod, node=node,
            cls=self.cls[-1] if self.cls else None, bare=name,
        )
        self.graph.add(info)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._add_def(node, node.name)
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._add_def(node, f"<lambda:{node.lineno}>")
        self.generic_visit(node)

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.scope.append(node.name)
        self.cls.append(node.name)
        self.generic_visit(node)
        self.cls.pop()
        self.scope.pop()


class CallGraph:
    """Package-wide def index + lazy call-edge resolution."""

    def __init__(self, modules: list[Module]):
        self.modules = modules
        self.defs: dict[str, FuncInfo] = {}
        self.by_bare: dict[str, list[FuncInfo]] = {}
        self.by_class_method: dict[tuple[str, str], list[FuncInfo]] = {}
        self.by_node: dict[int, FuncInfo] = {}
        self.imports: dict[str, dict[str, str]] = {
            m.name: {} for m in modules
        }
        for mod in modules:
            _ModuleIndex(mod, self).visit(mod.tree)
        # ``fn = lambda ...`` bindings: a jit application may wrap the
        # bound name (``fn = lambda p, s: ...; jax.jit(fn)``), so map
        # names to their lambda defs per module.
        self.lambda_binds: dict[str, dict[str, list[FuncInfo]]] = {}
        for mod in modules:
            binds = self.lambda_binds.setdefault(mod.name, {})
            for node in ast.walk(mod.tree):
                if (
                    isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Lambda)
                ):
                    info = self.by_node.get(id(node.value))
                    if info is None:
                        continue
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            binds.setdefault(t.id, []).append(info)

    def add(self, info: FuncInfo) -> None:
        self.defs[info.qual] = info
        self.by_bare.setdefault(info.bare, []).append(info)
        self.by_node[id(info.node)] = info
        if info.cls:
            self.by_class_method.setdefault(
                (info.cls, info.bare), []
            ).append(info)

    # -- jit roots ------------------------------------------------------------
    def jit_roots(self) -> list[tuple[FuncInfo, str]]:
        """Every function the package hands to ``jax.jit``, with the
        file:line of the application (for finding messages)."""
        roots: list[tuple[FuncInfo, str]] = []
        seen: set[str] = set()

        def note(info: FuncInfo | None, mod: Module, line: int) -> None:
            if info is not None and info.qual not in seen:
                seen.add(info.qual)
                roots.append((info, f"{mod.path}:{line}"))

        for mod in self.modules:
            for node in ast.walk(mod.tree):
                # @jax.jit / @functools.partial(jax.jit, ...) decorators
                if isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    for dec in node.decorator_list:
                        if _is_jit_expr(dec) or jit_application(dec):
                            for info in self.by_bare.get(node.name, []):
                                if info.node is node:
                                    note(info, mod, node.lineno)
                # jax.jit(fn, ...) calls
                app = jit_application(node)
                if app is None:
                    continue
                args = app.args
                if _is_jit_expr(app.func):
                    targets = args[:1]
                else:  # partial(jax.jit, fn?) — fn rarely positional
                    targets = args[1:2]
                for t in targets:
                    if isinstance(t, ast.Lambda):
                        for info in self.by_bare.get(
                            f"<lambda:{t.lineno}>", []
                        ):
                            if info.node is t:
                                note(info, mod, node.lineno)
                    elif isinstance(t, ast.Name):
                        resolved = self.resolve_call(
                            mod, t, enclosing=None
                        ) or self.lambda_binds.get(mod.name, {}).get(
                            t.id, []
                        )
                        for info in resolved:
                            note(info, mod, node.lineno)
        return roots

    # -- call resolution ------------------------------------------------------
    def _by_qual_or_bare(self, qual: str) -> list[FuncInfo]:
        if qual in self.defs:
            return [self.defs[qual]]
        bare = qual.rsplit(".", 1)[-1]
        cands = self.by_bare.get(bare, [])
        if 0 < len(cands) <= _MAX_FALLBACK:
            return cands
        return []

    def resolve_call(
        self,
        mod: Module,
        func: ast.AST,
        enclosing: FuncInfo | None,
    ) -> list[FuncInfo]:
        """Candidate definitions for a call expression's func."""
        imports = self.imports.get(mod.name, {})
        if isinstance(func, ast.Name):
            name = func.id
            # module-level def in the same module
            qual = f"{mod.name}.{name}"
            if qual in self.defs:
                return [self.defs[qual]]
            # nested def in the enclosing function
            if enclosing is not None:
                nested = f"{enclosing.qual}.{name}"
                if nested in self.defs:
                    return [self.defs[nested]]
            if name in imports:
                return self._by_qual_or_bare(imports[name])
            cands = self.by_bare.get(name, [])
            return cands if 0 < len(cands) <= _MAX_FALLBACK else []
        if isinstance(func, ast.Attribute):
            attr = func.attr
            base = func.value
            if isinstance(base, ast.Name):
                if base.id == "self" and enclosing is not None and enclosing.cls:
                    cands = self.by_class_method.get(
                        (enclosing.cls, attr), []
                    )
                    if cands:
                        return cands
                    return []
                if base.id in imports:  # module alias: mod.f(...)
                    return self._by_qual_or_bare(f"{imports[base.id]}.{attr}")
            # obj.method(...): match by method name across known classes,
            # only when rare and not a builtin/array method name.
            if attr in _ATTR_FALLBACK_DENY:
                return []
            cands = [
                c for c in self.by_bare.get(attr, []) if c.cls is not None
            ]
            return cands if 0 < len(cands) <= _MAX_FALLBACK else []
        return []

    def reachable(
        self, roots: list[tuple[FuncInfo, str]]
    ) -> dict[str, str]:
        """BFS the call graph from the jit roots. Returns
        ``{qual: root_description}`` for every reachable function."""
        out: dict[str, str] = {}
        frontier: list[tuple[FuncInfo, str]] = []
        for info, where in roots:
            if info.qual not in out:
                out[info.qual] = f"jitted at {where}"
                frontier.append((info, out[info.qual]))
        while frontier:
            info, origin = frontier.pop()
            body = (
                info.node.body
                if isinstance(info.node, (ast.FunctionDef, ast.AsyncFunctionDef))
                else [info.node.body]
            )
            for stmt in body:
                for node in ast.walk(stmt):
                    # nested defs/lambdas are walked as part of the outer
                    # function: inside jitted code they are scan bodies /
                    # branch arms that execute within the trace
                    if isinstance(node, ast.Call):
                        for cand in self.resolve_call(
                            info.module, node.func, enclosing=info
                        ):
                            if cand.qual not in out:
                                out[cand.qual] = origin
                                frontier.append((cand, origin))
        return out


def build_call_graph(modules: list[Module]) -> CallGraph:
    return CallGraph(modules)
