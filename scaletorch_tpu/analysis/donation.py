"""ST4xx — donation safety.

``donate_argnums`` lets XLA reuse an input buffer for an output — and
invalidates the Python-side array. Reading it afterwards returns
garbage or raises, depending on backend (CPU ignores donation, so the
bug ships: it only fires on TPU). The inference engine's donated KV
caches are exactly this hazard.

ST401  a name passed in a donated position of a jitted call is read
       again later in the same scope without being reassigned first

The resolver follows the factory idiom
(``step = make_paged_decode_step(…)``) across modules, so donated
positions declared in ``decode.py`` protect call sites in ``engine.py``.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from .core import Finding
from .scopes import (
    FuncNode,
    JitInfo,
    ModuleScopes,
    ProjectIndex,
    collect_jitted_callables,
    dotted_name,
)


def run(index: ProjectIndex) -> List[Finding]:
    findings: List[Finding] = []
    for ms in index.scopes.values():
        findings.extend(_check_module(index, ms))
    return findings


def _enclosing_body(ms: ModuleScopes, node: ast.AST) -> Optional[FuncNode]:
    cur = ms.parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur
        cur = ms.parents.get(cur)
    return None


def _donated_arg_names(call: ast.Call, info: JitInfo) -> List[str]:
    """Dotted names (``cache``, ``self.cache``) passed in donated
    positions."""
    out: List[str] = []
    donate_idx = info.donate_argnums or set()
    donate_names = info.donate_argnames or set()
    for i, arg in enumerate(call.args):
        if i in donate_idx:
            d = dotted_name(arg)
            if d:
                out.append(d)
    for kw in call.keywords:
        if kw.arg in donate_names:
            d = dotted_name(kw.value)
            if d:
                out.append(d)
    return out


def _assigned_names(stmt: ast.AST) -> Set[str]:
    """Dotted names (re)bound by a statement, including attribute
    targets like ``self.cache``."""
    names: Set[str] = set()
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, (ast.For, ast.AsyncFor)):
        targets = [stmt.target]
    for t in targets:
        for n in ast.walk(t):
            if isinstance(n, (ast.Name, ast.Attribute)):
                d = dotted_name(n)
                if d:
                    names.add(d)
    return names


def _check_module(index: ProjectIndex, ms: ModuleScopes) -> List[Finding]:
    jitted = collect_jitted_callables(index, ms)
    donating = {
        name: info for name, info in jitted.items()
        if (info.donate_argnums or info.donate_argnames)
    }
    if not donating:
        return []
    out: List[Finding] = []
    for call in ast.walk(ms.sm.tree):
        if not isinstance(call, ast.Call):
            continue
        cname = dotted_name(call.func)
        info = donating.get(cname) if cname else None
        if info is None:
            continue
        scope = _enclosing_body(ms, call)
        if scope is None:
            continue
        call_end = getattr(call, "end_lineno", call.lineno)
        rebound_here = _assigned_names(_enclosing_stmt(ms, call))
        for name in _donated_arg_names(call, info):
            if name in rebound_here:
                continue  # cache = step(..., cache): rebound by this very stmt
            finding = _read_after_donate(ms, scope, call_end, name)
            if finding is not None:
                out.append(finding)
    return out


def _enclosing_stmt(ms: ModuleScopes, node: ast.AST) -> ast.AST:
    cur: ast.AST = node
    while cur in ms.parents and not isinstance(cur, ast.stmt):
        cur = ms.parents[cur]
    return cur


def _read_after_donate(
    ms: ModuleScopes,
    scope: FuncNode,
    call_end: int,
    name: str,
) -> Optional[Finding]:
    """Line-ordered scan of the enclosing function: a Load of ``name``
    after the donating call, before any rebinding, is a use of a dead
    buffer."""
    events: List[tuple] = []  # (lineno, kind) kind: 0=assign, 1=load
    for node in ast.walk(scope):
        line = getattr(node, "lineno", None)
        if line is None or line <= call_end:
            continue
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign,
                             ast.For, ast.AsyncFor)):
            if name in _assigned_names(node):
                events.append((line, 0, node))
        if (
            isinstance(node, (ast.Name, ast.Attribute))
            and isinstance(getattr(node, "ctx", None), ast.Load)
            and dotted_name(node) == name
        ):
            events.append((line, 1, node))
    events.sort(key=lambda e: (e[0], e[1]))
    for line, kind, node in events:
        if kind == 0:
            # rebinding from an expression that READS the dead name is
            # still a bug (x = x + 1 after donate) — AugAssign or self-read
            if isinstance(node, ast.AugAssign):
                return _finding(ms, line, name)
            value = getattr(node, "value", None) or getattr(node, "iter", None)
            if value is not None and any(
                isinstance(n, (ast.Name, ast.Attribute))
                and dotted_name(n) == name
                for n in ast.walk(value)
            ):
                return _finding(ms, line, name)
            return None
        return _finding(ms, line, name)
    return None


def _finding(ms: ModuleScopes, line: int, name: str) -> Finding:
    return Finding(
        file=ms.sm.rel, line=line, code="ST401", severity="error",
        message=(
            f"'{name}' is read after being passed in a donated position — "
            "the buffer is invalidated by donate_argnums (works on CPU, "
            "garbage on TPU); rebind the result or drop donation"
        ),
    )
