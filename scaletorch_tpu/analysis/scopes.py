"""Jit-scope resolver + taint engine for jaxlint.

Two questions every pass keeps asking are answered here, once:

1. **Which code is traced?** A function body is a traced scope when it
   is decorated/wrapped with ``jax.jit``/``pjit``/``shard_map``, passed
   as the body of a ``lax`` higher-order primitive (``scan``/``cond``/
   ``while_loop``/``fori_loop``/``switch``), handed to a tracing
   transform (``vmap``/``grad``/``value_and_grad``/``checkpoint``/
   ``remat``/``custom_vjp``), or lexically nested inside any of the
   above (inner helpers trace with their parent). ``ProjectIndex``
   resolves this across the whole analyzed file set, including the
   factory idiom this codebase uses everywhere::

       def make_paged_decode_step(...):
           def decode(params, tokens, ...):
               ...
           return jax.jit(decode, donate_argnums=(5,))

   — ``decode`` is a jit scope, ``make_paged_decode_step`` is a *jit
   factory* and names bound from its call sites are jitted callables
   carrying the factory's static/donate argnums (imports followed
   module to module, best effort).

2. **Which values are tracers?** ``TaintTracker`` runs a linear,
   order-sensitive walk over a traced function body: parameters start
   tainted (minus ``static_argnums``/``static_argnames``), assignment
   propagates taint, reassignment from untainted expressions clears it.
   Static facts about a tracer — ``.shape``/``.ndim``/``.dtype``/
   ``.size``/``len()``/``isinstance()`` and ``is None`` tests — are
   sanitizers: branching on them is trace-time-safe and must not flag.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

from .core import SourceModule

FuncNode = Union[ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda]

# Callables whose function-valued argument is traced.
_JIT_WRAPPERS = {"jit", "pjit"}
_SHARD_WRAPPERS = {"shard_map"}
_TRACING_TRANSFORMS = {
    "vmap", "pmap", "grad", "value_and_grad", "checkpoint", "remat",
    "custom_vjp", "custom_jvp", "linearize", "jvp", "vjp", "hessian",
    "jacfwd", "jacrev",
}
# lax.<hof>(body, ...) — argument index -> which positions hold bodies.
_LAX_HOFS = {
    "scan": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "cond": (1, 2),
    "switch": None,  # every arg after the index may be a branch
    "associative_scan": (0,),
    "map": (0,),
    "custom_root": (0, 1, 2),
}

_SANITIZER_ATTRS = {
    "shape", "ndim", "dtype", "size", "itemsize", "sharding", "aval",
    "nbytes", "weak_type",
}
_SANITIZER_CALLS = {"len", "isinstance", "type", "hasattr", "getattr", "id"}


def dotted_name(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for Attribute/Name chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted_name(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def tail_name(node: ast.AST) -> Optional[str]:
    """Last component of a dotted name ('scan' for jax.lax.scan)."""
    d = dotted_name(node)
    return d.rsplit(".", 1)[-1] if d else None


def _const_int_set(node: Optional[ast.AST]) -> Optional[Set[int]]:
    """Evaluate a static_argnums/donate_argnums literal. None = dynamic."""
    if node is None:
        return set()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[int] = set()
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.add(el.value)
            else:
                return None
        return out
    if isinstance(node, ast.IfExp):
        # the `(4,) if donate else ()` idiom: union both arms (conservative)
        a = _const_int_set(node.body)
        b = _const_int_set(node.orelse)
        return None if a is None or b is None else a | b
    return None


def _const_str_set(node: Optional[ast.AST]) -> Optional[Set[str]]:
    if node is None:
        return set()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.add(el.value)
            else:
                return None
        return out
    return None


@dataclasses.dataclass
class JitInfo:
    """How one function/callable is traced."""

    kind: str  # "jit" | "shard_map" | "lax_body" | "transform"
    node: Optional[FuncNode] = None
    # None means "declared but not statically evaluable" (dynamic expr).
    static_argnums: Optional[Set[int]] = dataclasses.field(default_factory=set)
    static_argnames: Optional[Set[str]] = dataclasses.field(default_factory=set)
    donate_argnums: Optional[Set[int]] = dataclasses.field(default_factory=set)
    donate_argnames: Optional[Set[str]] = dataclasses.field(default_factory=set)

    def merged_with_call(self, call: ast.Call) -> "JitInfo":
        """JitInfo for ``jax.jit(f, static_argnums=..., donate_argnums=...)``."""
        info = JitInfo(kind=self.kind, node=self.node)
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                info.static_argnums = _const_int_set(kw.value)
            elif kw.arg == "static_argnames":
                info.static_argnames = _const_str_set(kw.value)
            elif kw.arg == "donate_argnums":
                info.donate_argnums = _const_int_set(kw.value)
            elif kw.arg == "donate_argnames":
                info.donate_argnames = _const_str_set(kw.value)
        return info


def _is_jit_callable(call_func: ast.AST) -> bool:
    return tail_name(call_func) in _JIT_WRAPPERS


def _is_shard_map(call_func: ast.AST) -> bool:
    return tail_name(call_func) in _SHARD_WRAPPERS


def _is_transform(call_func: ast.AST) -> bool:
    return tail_name(call_func) in _TRACING_TRANSFORMS


def _lax_body_positions(call_func: ast.AST) -> Optional[Tuple[int, ...]]:
    t = tail_name(call_func)
    if t not in _LAX_HOFS:
        return None
    d = dotted_name(call_func) or t
    # accept lax.scan / jax.lax.scan / bare scan-from-lax-import
    if "." in d and not (d.endswith(f"lax.{t}")):
        return None
    pos = _LAX_HOFS[t]
    return tuple(range(8)) if pos is None else pos


class ModuleScopes:
    """Per-module scope facts: traced functions, jitted names, factories."""

    def __init__(self, sm: SourceModule) -> None:
        self.sm = sm
        self.parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(sm.tree):
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent
        # every named function, by (possibly shadowed) bare name, innermost last
        self.functions: Dict[str, List[ast.FunctionDef]] = {}
        for node in ast.walk(sm.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions.setdefault(node.name, []).append(node)
        # directly-traced function nodes -> JitInfo
        self.traced: Dict[FuncNode, JitInfo] = {}
        # module-level callable names known to be jitted (g = jax.jit(f, ...))
        self.jitted_names: Dict[str, JitInfo] = {}
        # top-level functions that RETURN a jitted callable
        self.factories: Dict[str, JitInfo] = {}
        # import map: local name -> (module, original name)
        self.imports: Dict[str, Tuple[str, str]] = {}
        self._collect_imports()
        self._collect_traced()
        self._collect_factories()

    # -- imports --------------------------------------------------------------
    def _collect_imports(self) -> None:
        pkg_parts = self.sm.module.split(".")
        for node in ast.walk(self.sm.tree):
            if isinstance(node, ast.ImportFrom):
                if node.level:  # relative import
                    base = pkg_parts[: len(pkg_parts) - node.level]
                    mod = ".".join(base + ([node.module] if node.module else []))
                else:
                    mod = node.module or ""
                for alias in node.names:
                    self.imports[alias.asname or alias.name] = (mod, alias.name)

    # -- traced scopes --------------------------------------------------------
    def _resolve_local_fn(self, name_node: ast.AST, at: ast.AST) -> Optional[ast.FunctionDef]:
        """Resolve a Name argument to the function it most plausibly
        references (same bare name; prefer a sibling in the same scope)."""
        if isinstance(name_node, ast.Lambda):
            return None
        if not isinstance(name_node, ast.Name):
            return None
        cands = self.functions.get(name_node.id)
        if not cands:
            return None
        if len(cands) == 1:
            return cands[0]
        enclosing = self._enclosing_function(at)
        for c in cands:
            if self._enclosing_function(c) is enclosing:
                return c
        return cands[-1]

    def _enclosing_function(self, node: ast.AST) -> Optional[FuncNode]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                return cur
            cur = self.parents.get(cur)
        return None

    def _mark(self, fn: Optional[FuncNode], info: JitInfo) -> None:
        if fn is None:
            return
        prev = self.traced.get(fn)
        if prev is None or (prev.kind != "jit" and info.kind == "jit"):
            self.traced[fn] = info

    def _collect_traced(self) -> None:
        for node in ast.walk(self.sm.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    info = self._decorator_jit_info(dec)
                    if info is not None:
                        self._mark(node, info)
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if _is_jit_callable(func) or _is_shard_map(func):
                kind = "jit" if _is_jit_callable(func) else "shard_map"
                info = JitInfo(kind=kind).merged_with_call(node)
                target = node.args[0] if node.args else None
                if isinstance(target, ast.Lambda):
                    info.node = target
                    self._mark(target, info)
                else:
                    fn = self._resolve_local_fn(target, node) if target else None
                    if fn is not None:
                        info.node = fn
                        self._mark(fn, info)
                # g = jax.jit(f, ...) binds a jitted callable name
                parent = self.parents.get(node)
                if isinstance(parent, ast.Assign):
                    for t in parent.targets:
                        if isinstance(t, ast.Name):
                            self.jitted_names[t.id] = info
            elif _is_transform(func):
                target = node.args[0] if node.args else None
                if isinstance(target, ast.Lambda):
                    self._mark(target, JitInfo(kind="transform", node=target))
                else:
                    fn = self._resolve_local_fn(target, node) if target else None
                    if fn is not None:
                        self._mark(fn, JitInfo(kind="transform", node=fn))
            else:
                positions = _lax_body_positions(func)
                if positions is not None:
                    for i in positions:
                        if i >= len(node.args):
                            break
                        arg = node.args[i]
                        if isinstance(arg, ast.Lambda):
                            self._mark(arg, JitInfo(kind="lax_body", node=arg))
                        else:
                            fn = self._resolve_local_fn(arg, node)
                            if fn is not None:
                                self._mark(fn, JitInfo(kind="lax_body", node=fn))

    def _decorator_jit_info(self, dec: ast.AST) -> Optional[JitInfo]:
        if _is_jit_callable(dec) or _is_shard_map(dec):
            return JitInfo(kind="jit" if _is_jit_callable(dec) else "shard_map")
        if isinstance(dec, ast.Call):
            if _is_jit_callable(dec.func) or _is_shard_map(dec.func):
                kind = "jit" if _is_jit_callable(dec.func) else "shard_map"
                return JitInfo(kind=kind).merged_with_call(dec)
            # @partial(jax.jit, static_argnames=...)
            if tail_name(dec.func) == "partial" and dec.args:
                inner = dec.args[0]
                if _is_jit_callable(inner) or _is_shard_map(inner):
                    kind = "jit" if _is_jit_callable(inner) else "shard_map"
                    return JitInfo(kind=kind).merged_with_call(dec)
                if _is_transform(inner):
                    return JitInfo(kind="transform")
        if _is_transform(dec):
            return JitInfo(kind="transform")
        return None

    # -- factories ------------------------------------------------------------
    def _returned_jit_info(self, fn: ast.FunctionDef) -> Optional[JitInfo]:
        for node in ast.walk(fn):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            v = node.value
            if isinstance(v, ast.Call) and (_is_jit_callable(v.func) or _is_shard_map(v.func)):
                kind = "jit" if _is_jit_callable(v.func) else "shard_map"
                return JitInfo(kind=kind).merged_with_call(v)
            # `return step` where step = jax.jit(...) earlier in the body
            if isinstance(v, ast.Name):
                for inner in ast.walk(fn):
                    if (
                        isinstance(inner, ast.Assign)
                        and isinstance(inner.value, ast.Call)
                        and (_is_jit_callable(inner.value.func)
                             or _is_shard_map(inner.value.func))
                        and any(isinstance(t, ast.Name) and t.id == v.id
                                for t in inner.targets)
                    ):
                        kind = ("jit" if _is_jit_callable(inner.value.func)
                                else "shard_map")
                        return JitInfo(kind=kind).merged_with_call(inner.value)
        return None

    def _collect_factories(self) -> None:
        for node in self.sm.tree.body:
            if isinstance(node, ast.FunctionDef):
                info = self._returned_jit_info(node)
                if info is not None:
                    self.factories[node.name] = info

    # -- queries --------------------------------------------------------------
    def is_traced(self, fn: FuncNode) -> Optional[JitInfo]:
        """JitInfo if ``fn`` or any lexical ancestor is a traced scope."""
        cur: Optional[ast.AST] = fn
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                info = self.traced.get(cur)
                if info is not None:
                    # nested helpers inherit tracedness but not argnums
                    if cur is fn:
                        return info
                    return JitInfo(kind=info.kind, node=fn)
            cur = self.parents.get(cur)
        return None

    def traced_functions(self) -> List[Tuple[FuncNode, JitInfo]]:
        """Every function body that traces, including nested helpers."""
        out: List[Tuple[FuncNode, JitInfo]] = []
        for node in ast.walk(self.sm.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                info = self.is_traced(node)
                if info is not None:
                    out.append((node, info))
        return out


class ProjectIndex:
    """Cross-module facts shared by all passes."""

    def __init__(self, modules: Sequence[SourceModule]) -> None:
        self.modules = list(modules)
        self.scopes: Dict[str, ModuleScopes] = {
            sm.rel: ModuleScopes(sm) for sm in modules
        }
        self.by_module: Dict[str, ModuleScopes] = {
            ms.sm.module: ms for ms in self.scopes.values()
        }
        self.declared_axes: Set[str] = self._find_declared_axes()
        self.param_keys: Set[str] = self._collect_param_keys()

    # -- mesh axes ------------------------------------------------------------
    def _find_declared_axes(self) -> Set[str]:
        """Axis names from ``MESH_AXES = (...)`` in the analyzed set; the
        sharding pass falls back to the package source when linting a
        subset that excludes parallel/mesh.py."""
        for sm in self.modules:
            axes = find_mesh_axes(sm.tree)
            if axes:
                return axes
        return set()

    # -- param-key universe ---------------------------------------------------
    def _collect_param_keys(self) -> Set[str]:
        """All string dict keys used OUTSIDE ``*_specs`` functions — the
        universe a spec tree's keys must reference."""
        keys: Set[str] = set()
        for ms in self.scopes.values():
            spec_fns = [
                fns[-1] for name, fns in ms.functions.items()
                if name.endswith("_specs")
            ]
            spec_nodes: Set[ast.AST] = set()
            for fn in spec_fns:
                spec_nodes.update(ast.walk(fn))
            for node in ast.walk(ms.sm.tree):
                if node in spec_nodes:
                    continue
                if isinstance(node, ast.Dict):
                    for k in node.keys:
                        if isinstance(k, ast.Constant) and isinstance(k.value, str):
                            keys.add(k.value)
                elif isinstance(node, ast.Subscript):
                    sl = node.slice
                    if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                        keys.add(sl.value)
        return keys

    # -- jitted-callable resolution -------------------------------------------
    def resolve_factory(self, ms: ModuleScopes, call_func: ast.AST) -> Optional[JitInfo]:
        """JitInfo when ``call_func`` names a jit factory (local or
        imported), else None."""
        name = dotted_name(call_func)
        if name is None:
            return None
        bare = name.rsplit(".", 1)[-1]
        if name in ms.factories or bare in ms.factories:
            return ms.factories.get(name) or ms.factories[bare]
        imp = ms.imports.get(name) or ms.imports.get(bare)
        if imp is not None:
            target = self.by_module.get(imp[0])
            if target is not None and imp[1] in target.factories:
                return target.factories[imp[1]]
        return None


def find_mesh_axes(tree: ast.Module) -> Optional[Set[str]]:
    """``MESH_AXES`` value from a module, handling both plain and
    annotated assignment (the package uses ``MESH_AXES: tuple[...] = …``)."""
    for node in ast.walk(tree):
        value: Optional[ast.AST] = None
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == "MESH_AXES" for t in node.targets
        ):
            value = node.value
        elif (
            isinstance(node, ast.AnnAssign)
            and isinstance(node.target, ast.Name)
            and node.target.id == "MESH_AXES"
        ):
            value = node.value
        if value is not None:
            axes = _const_str_set(value)
            if axes:
                return axes
    return None


def collect_jitted_callables(
    index: ProjectIndex, ms: ModuleScopes
) -> Dict[str, JitInfo]:
    """Names in ``ms`` bound to jitted callables, keyed by the dotted
    name call sites use (``step``, ``self._decode`` …).

    Covers direct wrapping (``g = jax.jit(f, …)``) and the factory
    idiom (``g = make_paged_decode_step(…)`` where the factory — local or
    imported — returns a ``jax.jit``-wrapped function), so the donation
    and retrace passes see the same callables the runtime does.
    """
    out: Dict[str, JitInfo] = dict(ms.jitted_names)
    for node in ast.walk(ms.sm.tree):
        if not isinstance(node, ast.Assign) or not isinstance(node.value, ast.Call):
            continue
        call = node.value
        info: Optional[JitInfo] = None
        if _is_jit_callable(call.func) or _is_shard_map(call.func):
            kind = "jit" if _is_jit_callable(call.func) else "shard_map"
            info = JitInfo(kind=kind).merged_with_call(call)
        else:
            info = index.resolve_factory(ms, call.func)
        if info is None:
            continue
        for t in node.targets:
            name = dotted_name(t)
            if name:
                out[name] = info
    return out


# ---- taint ------------------------------------------------------------------

class TaintTracker:
    """Order-sensitive tracer-taint tracking for one traced function."""

    def __init__(self, fn: FuncNode, info: JitInfo) -> None:
        self.fn = fn
        self.tainted: Set[str] = set()
        args = fn.args
        names: List[str] = [a.arg for a in args.posonlyargs + args.args]
        static_idx = info.static_argnums if info.static_argnums is not None else set()
        static_names = info.static_argnames if info.static_argnames is not None else set()
        for i, n in enumerate(names):
            if i in static_idx or n in static_names:
                continue
            self.tainted.add(n)
        for a in args.kwonlyargs:
            if a.arg not in static_names:
                self.tainted.add(a.arg)
        if args.vararg:
            self.tainted.add(args.vararg.arg)
        if args.kwarg:
            self.tainted.add(args.kwarg.arg)
        # `self` in methods is config, not a tracer
        self.tainted.discard("self")
        # names bound to lambdas that map tracers to static facts
        # (vma_of = lambda x: jax.typeof(x).vma …)
        self.sanitizer_names: Set[str] = set()

    # -- expression tainting --------------------------------------------------
    def is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _SANITIZER_ATTRS:
                return False
            return self.is_tainted(node.value)
        if isinstance(node, ast.Call):
            fname = tail_name(node.func)
            if fname in _SANITIZER_CALLS or fname in self.sanitizer_names \
                    or fname == "typeof":
                return False
            return (
                any(self.is_tainted(a) for a in node.args)
                or any(self.is_tainted(kw.value) for kw in node.keywords)
                or (isinstance(node.func, ast.Attribute)
                    and self.is_tainted(node.func.value))
            )
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            return self._comprehension_tainted(node)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a static structure test
            if (
                all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops)
                and all(isinstance(c, ast.Constant) and c.value is None
                        for c in node.comparators)
            ):
                return False
            return self.is_tainted(node.left) or any(
                self.is_tainted(c) for c in node.comparators
            )
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return False
        return any(
            self.is_tainted(child) for child in ast.iter_child_nodes(node)
        )

    def _comprehension_tainted(self, node: ast.AST) -> bool:
        """A comprehension's taint is its ELEMENT expression's taint with
        the comprehension targets tainted from their iterables — not the
        iterable's taint itself ([f(x) for x in leaves] is untainted when
        f maps tracers to static facts)."""
        saved = set(self.tainted)
        try:
            for gen in node.generators:
                self._observe_loop(gen.target, gen.iter)
            for gen in node.generators:
                if any(self.is_tainted(cond) for cond in gen.ifs):
                    return True
            if isinstance(node, ast.DictComp):
                return self.is_tainted(node.key) or self.is_tainted(node.value)
            return self.is_tainted(node.elt)
        finally:
            self.tainted = saved

    # -- statement effects ----------------------------------------------------
    def _assign_target_names(self, target: ast.AST) -> List[str]:
        if isinstance(target, ast.Name):
            return [target.id]
        if isinstance(target, (ast.Tuple, ast.List)):
            out: List[str] = []
            for el in target.elts:
                out.extend(self._assign_target_names(el))
            return out
        if isinstance(target, ast.Starred):
            return self._assign_target_names(target.value)
        return []

    def observe(self, stmt: ast.stmt) -> None:
        """Update taint for one top-level statement (no recursion into
        compound bodies — callers walk those explicitly)."""
        if isinstance(stmt, ast.Assign):
            if isinstance(stmt.value, ast.Lambda):
                self._observe_lambda_alias(stmt)
                return
            t = self.is_tainted(stmt.value)
            for target in stmt.targets:
                for name in self._assign_target_names(target):
                    (self.tainted.add if t else self.tainted.discard)(name)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            if isinstance(stmt.target, ast.Name):
                t = self.is_tainted(stmt.value)
                (self.tainted.add if t else self.tainted.discard)(stmt.target.id)
        elif isinstance(stmt, ast.AugAssign):
            if isinstance(stmt.target, ast.Name) and self.is_tainted(stmt.value):
                self.tainted.add(stmt.target.id)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._observe_loop(stmt.target, stmt.iter)
        elif isinstance(stmt, ast.With):
            for item in stmt.items:
                if item.optional_vars is not None and self.is_tainted(item.context_expr):
                    for name in self._assign_target_names(item.optional_vars):
                        self.tainted.add(name)

    def _observe_lambda_alias(self, stmt: ast.Assign) -> None:
        """``f = lambda x: <expr>``: if <expr> is untainted even with the
        lambda's params tainted, ``f(...)`` maps tracers to static facts
        and becomes a sanitizer for this scope."""
        lam = stmt.value
        assert isinstance(lam, ast.Lambda)
        saved = set(self.tainted)
        try:
            for a in lam.args.posonlyargs + lam.args.args + lam.args.kwonlyargs:
                self.tainted.add(a.arg)
            body_tainted = self.is_tainted(lam.body)
        finally:
            self.tainted = saved
        for target in stmt.targets:
            for name in self._assign_target_names(target):
                self.tainted.discard(name)
                if not body_tainted:
                    self.sanitizer_names.add(name)
                else:
                    self.sanitizer_names.discard(name)

    def _observe_loop(self, target: ast.AST, iter_expr: ast.AST) -> None:
        """Taint loop targets from the iterable — element-wise through
        ``zip``/``enumerate`` so iterating a traced pytree alongside a
        static host list doesn't taint the static elements."""
        if (
            isinstance(iter_expr, ast.Call)
            and isinstance(iter_expr.func, ast.Name)
            and isinstance(target, ast.Tuple)
        ):
            fname = iter_expr.func.id
            if fname == "zip" and len(iter_expr.args) == len(target.elts):
                for src, tgt in zip(iter_expr.args, target.elts):
                    self._observe_loop(tgt, src)
                return
            if fname == "enumerate" and len(target.elts) == 2 and iter_expr.args:
                for name in self._assign_target_names(target.elts[0]):
                    self.tainted.discard(name)
                self._observe_loop(target.elts[1], iter_expr.args[0])
                return
        t = self.is_tainted(iter_expr)
        for name in self._assign_target_names(target):
            (self.tainted.add if t else self.tainted.discard)(name)
