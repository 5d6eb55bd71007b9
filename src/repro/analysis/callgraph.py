"""AST module index + best-effort call graph over ``src/repro``.

The static rules (:mod:`repro.analysis.rules_trace`, ``rules_dispatch``,
``rules_concurrency``) need three global facts no single-file linter can
compute:

* which functions are *trace roots* — wrapped in ``jax.jit`` (decorator,
  ``functools.partial(jax.jit, ...)``, or an inline ``jax.jit(fn)`` /
  ``jax.jit(lambda ...)``), or handed to another tracing transform
  (``vmap``/``scan``/``shard_map``/...), so their bodies run under
  tracers;
* which functions are *trace-reachable* — called (directly, through a
  locally defined helper, or referenced as a function argument) from a
  trace root, so a host sync inside them silently lands on a jitted hot
  path;
* which functions can *launch a Pallas kernel* — reach a
  ``pl.pallas_call`` through the same edges — so a ``jax.vmap`` over one
  can be flagged (the PR 1/PR 6 "never Pallas under vmap" invariant).

Resolution is intentionally best-effort and *overapproximating*: a name
that cannot be resolved contributes no edge (no false reachability), a
function reference passed anywhere contributes an edge whether or not it
is ultimately invoked (reachability never under-reports on the hot
paths, which is the failure mode that matters for a gate).  Method calls
through ``self`` resolve within the class; calls through arbitrary
objects do not resolve and are dropped.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

__all__ = ["FunctionInfo", "ModuleInfo", "CallGraph", "build_graph",
           "dotted_parts", "TRACE_WRAPPERS", "PALLAS_CALL"]

# transforms that trace the function handed to them: jit compilation or a
# tracer-driven transform (either way the wrapped body sees tracers, so
# trace-safety rules apply to everything reachable from it)
TRACE_WRAPPERS = frozenset({
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.lax.scan", "jax.lax.map",
    "jax.lax.while_loop", "jax.lax.cond", "jax.lax.fori_loop",
    "jax.lax.switch", "jax.lax.associative_scan",
    "jax.shard_map",
})

# the Pallas launch entry point (``pl.pallas_call`` under the canonical
# ``from jax.experimental import pallas as pl`` import)
PALLAS_CALL = frozenset({
    "jax.experimental.pallas.pallas_call",
})

_VMAP = frozenset({"jax.vmap"})


@dataclasses.dataclass
class FunctionInfo:
    """One function-like scope: def, method, nested def, or lambda."""

    qualname: str                      # repro.core.pq.encode / ...Cls.meth
    module: "ModuleInfo"
    node: ast.AST                      # FunctionDef | AsyncFunctionDef | Lambda
    lineno: int
    class_qual: Optional[str] = None   # enclosing class qualname, if a method
    parent: Optional[str] = None       # enclosing function qualname
    is_trace_root: bool = False
    # static_argnames attached by a jit wrapper (names, wrapper lineno)
    jit_static: Optional[Tuple[Tuple[str, ...], int]] = None
    calls: Set[str] = dataclasses.field(default_factory=set)
    refs: Set[str] = dataclasses.field(default_factory=set)

    @property
    def params(self) -> Set[str]:
        a = self.node.args
        names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return set(names)


@dataclasses.dataclass
class ModuleInfo:
    qualname: str                      # repro.index.streaming
    path: Path
    tree: ast.Module
    source: str
    imports: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class VmapSite:
    """One ``jax.vmap(fn)`` call: who vmapped what, and where."""

    caller: str                        # enclosing scope qualname
    target: Optional[str]              # resolved fn qualname (None: unknown)
    module: ModuleInfo
    lineno: int


class CallGraph:
    """The module/function index plus derived reachability sets."""

    def __init__(self) -> None:
        self.modules: Dict[str, ModuleInfo] = {}
        self.functions: Dict[str, FunctionInfo] = {}
        self.vmap_sites: List[VmapSite] = []
        # (function qual, local name) -> lambda/def qualname for
        # ``fn = lambda ...`` aliases
        self._local_alias: Dict[Tuple[str, str], str] = {}

    # -- reachability --------------------------------------------------------

    def edges(self, qual: str) -> Set[str]:
        fn = self.functions.get(qual)
        if fn is None:
            return set()
        return {c for c in fn.calls | fn.refs if c in self.functions}

    def reachable_from(self, roots) -> Set[str]:
        seen, todo = set(), [r for r in roots if r in self.functions]
        while todo:
            q = todo.pop()
            if q in seen:
                continue
            seen.add(q)
            todo.extend(self.edges(q) - seen)
        return seen

    def trace_roots(self) -> Set[str]:
        return {q for q, f in self.functions.items() if f.is_trace_root}

    def trace_reachable(self) -> Set[str]:
        return self.reachable_from(self.trace_roots())

    def pallas_launchers(self) -> Set[str]:
        return {q for q, f in self.functions.items()
                if f.calls & PALLAS_CALL}

    def reaches_pallas(self) -> Set[str]:
        """Every function from which a ``pallas_call`` is reachable."""
        launchers = self.pallas_launchers()
        out = set(launchers)
        # iterate to fixpoint over the (small) function set
        changed = True
        while changed:
            changed = False
            for q in self.functions:
                if q in out:
                    continue
                if self.edges(q) & out:
                    out.add(q)
                    changed = True
        return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def dotted_parts(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` -> ["a", "b", "c"]; None for anything richer."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


def _module_qualname(path: Path, src_root: Path) -> str:
    rel = path.relative_to(src_root).with_suffix("")
    parts = list(rel.parts)
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _resolve_imports(mod_qual: str, tree: ast.Module) -> Dict[str, str]:
    pkg_parts = mod_qual.split(".")[:-1]
    imports: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                imports[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = pkg_parts[:len(pkg_parts) - node.level + 1]
                prefix = ".".join(base + ([node.module] if node.module
                                          else []))
            else:
                prefix = node.module or ""
            for a in node.names:
                if a.name == "*":
                    continue
                target = f"{prefix}.{a.name}" if prefix else a.name
                imports[a.asname or a.name] = target
    return imports


class _Indexer(ast.NodeVisitor):
    """Pass 1: register every function-like scope."""

    def __init__(self, graph: CallGraph, module: ModuleInfo):
        self.g = graph
        self.m = module
        self.scope: List[str] = [module.qualname]
        self.class_stack: List[str] = []
        self.fn_stack: List[str] = []

    def _register(self, node, name: str) -> FunctionInfo:
        qual = f"{self.scope[-1]}.{name}"
        info = FunctionInfo(
            qualname=qual, module=self.m, node=node, lineno=node.lineno,
            class_qual=self.class_stack[-1] if self.class_stack else None,
            parent=self.fn_stack[-1] if self.fn_stack else None)
        self.g.functions[qual] = info
        if self.fn_stack:
            # containment edge: a nested scope is treated as reachable
            # from its parent (overapproximation, see module docstring)
            self.g.functions[self.fn_stack[-1]].refs.add(qual)
        return info

    def visit_ClassDef(self, node: ast.ClassDef):
        qual = f"{self.scope[-1]}.{node.name}"
        self.scope.append(qual)
        self.class_stack.append(qual)
        self.generic_visit(node)
        self.class_stack.pop()
        self.scope.pop()

    def _visit_function(self, node):
        info = self._register(node, node.name)
        self._apply_decorators(info, node)
        self.scope.append(info.qualname)
        self.fn_stack.append(info.qualname)
        self.generic_visit(node)
        self.fn_stack.pop()
        self.scope.pop()

    visit_FunctionDef = _visit_function
    visit_AsyncFunctionDef = _visit_function

    def visit_Lambda(self, node: ast.Lambda):
        info = self._register(node, f"<lambda@{node.lineno}>")
        self.scope.append(info.qualname)
        self.fn_stack.append(info.qualname)
        self.generic_visit(node)
        self.fn_stack.pop()
        self.scope.pop()

    def visit_Assign(self, node: ast.Assign):
        # ``fn = lambda ...`` / ``fn = helper``: remember the local alias so
        # ``jax.vmap(fn)`` can resolve through it
        if (self.fn_stack and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            name = node.targets[0].id
            if isinstance(node.value, ast.Lambda):
                lam = f"{self.scope[-1]}.<lambda@{node.value.lineno}>"
                self.g._local_alias[(self.fn_stack[-1], name)] = lam
        self.generic_visit(node)

    def _apply_decorators(self, info: FunctionInfo, node) -> None:
        for dec in node.decorator_list:
            target, static = _unwrap_jit_expr(dec, self.m.imports)
            if target == "__decorated__":
                info.is_trace_root = True
                if static is not None:
                    info.jit_static = (static, dec.lineno)


def _resolve_external(parts: List[str], imports: Dict[str, str]
                      ) -> Optional[str]:
    if parts and parts[0] in imports:
        return ".".join([imports[parts[0]]] + parts[1:])
    return None


def _unwrap_jit_expr(node: ast.AST, imports: Dict[str, str]):
    """Recognize a jit/tracing wrapper used as a decorator.

    Returns ``("__decorated__", static_argnames or None)`` when ``node``
    is ``jax.jit`` / ``functools.partial(jax.jit, ...)`` / a call of
    either; ``(None, None)`` otherwise.
    """
    parts = dotted_parts(node)
    if parts is not None:
        qual = _resolve_external(parts, imports) or ".".join(parts)
        if qual in TRACE_WRAPPERS:
            return "__decorated__", None
        return None, None
    if isinstance(node, ast.Call):
        fparts = dotted_parts(node.func)
        fqual = (_resolve_external(fparts, imports) or ".".join(fparts)
                 if fparts else "")
        if fqual in ("functools.partial", "partial") and node.args:
            inner = dotted_parts(node.args[0])
            iqual = (_resolve_external(inner, imports) or ".".join(inner)
                     if inner else "")
            if iqual in TRACE_WRAPPERS:
                return "__decorated__", _static_argnames(node)
        if fqual in TRACE_WRAPPERS:
            return "__decorated__", _static_argnames(node)
    return None, None


def _static_argnames(call: ast.Call) -> Optional[Tuple[str, ...]]:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names: List[str] = []
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                names.append(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    if isinstance(el, ast.Constant) and isinstance(
                            el.value, str):
                        names.append(el.value)
            return tuple(names)
    return None


class _Resolver(ast.NodeVisitor):
    """Pass 2: resolve calls/references inside one function scope."""

    def __init__(self, graph: CallGraph, info: FunctionInfo):
        self.g = graph
        self.info = info

    def resolve(self, node: ast.AST) -> Optional[str]:
        parts = dotted_parts(node)
        if parts is None:
            if isinstance(node, ast.Lambda):
                return f"{self.info.qualname}.<lambda@{node.lineno}>"
            return None
        m = self.info.module
        head = parts[0]
        if head == "self" and self.info.class_qual and len(parts) > 1:
            return f"{self.info.class_qual}.{parts[1]}"
        # local lambda aliases, innermost scope first
        scope: Optional[str] = self.info.qualname
        while scope is not None:
            alias = self.g._local_alias.get((scope, head))
            if alias is not None:
                return alias
            cand = f"{scope}.{head}"
            if cand in self.g.functions:
                return ".".join([cand] + parts[1:]) if len(parts) > 1 \
                    else cand
            scope = self.g.functions[scope].parent \
                if scope in self.g.functions else None
        mod_cand = f"{m.qualname}.{head}"
        if mod_cand in self.g.functions:
            return ".".join([mod_cand] + parts[1:]) if len(parts) > 1 \
                else mod_cand
        if len(parts) > 1 and mod_cand in {f.class_qual for f in
                                           self.g.functions.values()
                                           if f.class_qual}:
            return f"{mod_cand}.{parts[1]}"
        ext = _resolve_external(parts, m.imports)
        if ext is not None:
            return ext
        return ".".join(parts)

    def _body_nodes(self):
        """Walk the scope's own statements, not nested function bodies."""
        todo = list(ast.iter_child_nodes(self.info.node))
        while todo:
            n = todo.pop()
            yield n
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            todo.extend(ast.iter_child_nodes(n))

    def run(self) -> None:
        for n in self._body_nodes():
            if isinstance(n, ast.Call):
                self._handle_call(n)

    def _handle_call(self, node: ast.Call) -> None:
        qual = self.resolve(node.func)
        if qual is not None:
            self.info.calls.add(qual)
        # function references handed as arguments (vmap/scan/jit/callbacks)
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            r = self.resolve(arg)
            if r is not None and r in self.g.functions:
                self.info.refs.add(r)
        if qual in TRACE_WRAPPERS and node.args:
            target = self.resolve(node.args[0])
            if target is not None and target in self.g.functions:
                tinfo = self.g.functions[target]
                tinfo.is_trace_root = True
                if qual == "jax.jit":
                    static = _static_argnames(node)
                    if static is not None and tinfo.jit_static is None:
                        tinfo.jit_static = (static, node.lineno)
        if qual in _VMAP and node.args:
            target = self.resolve(node.args[0])
            self.g.vmap_sites.append(VmapSite(
                caller=self.info.qualname,
                target=target if target in self.g.functions else None,
                module=self.info.module, lineno=node.lineno))


def build_graph(py_files, src_root: Path) -> CallGraph:
    """Index ``py_files`` (under ``src_root``, e.g. ``<repo>/src``) into a
    :class:`CallGraph` with calls resolved and trace roots marked."""
    g = CallGraph()
    for path in py_files:
        source = path.read_text(encoding="utf-8")
        tree = ast.parse(source, filename=str(path))
        qual = _module_qualname(path, src_root)
        mod = ModuleInfo(qualname=qual, path=path, tree=tree, source=source)
        mod.imports = _resolve_imports(qual, tree)
        g.modules[qual] = mod
        _Indexer(g, mod).visit(tree)
    for info in list(g.functions.values()):
        _Resolver(g, info).run()
    return g
