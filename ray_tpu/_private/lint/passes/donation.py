"""donation-use-after: reads of a binding after it flowed into a
donated jit position.

``donate_argnums`` hands the argument's HBM to XLA for reuse: after the
call returns, the old buffer may already hold activations of the *next*
step.  Reading the donated binding afterwards is not an error anywhere
— on CPU backends it "works", under ``jit`` tracing it sometimes works,
on a TPU pod it silently reads freed HBM.  That makes it the perfect
lint target: trivially fatal, invisible to tests off-pod.

The pass runs a may-analysis over the per-function CFG: a binding that
flowed into a donated position *on some path* is poisoned until rebound,
and any later read (including attribute reads ``state.params`` and
writes into its fields ``state.field = x``) is a finding.  Donating
callables are recognized three ways:

- names assigned a ``jax.jit`` / ``pjit`` / ``tracked_jit`` result with
  a literal ``donate_argnums`` in any lexically enclosing scope
  (``fn = jax.jit(step, donate_argnums=(0,)); fn(state, batch)``);
- ``self.X`` attributes assigned such a result anywhere in the class
  (the serve engine's pattern, ``serve/llm/programs.py``: the object
  that wraps ``self._jit_tick`` owns the arrays it donates, and its
  ``tick()`` hands ``self._cache`` in and rebinds it from the result);
- one level of interprocedural summary: a function whose *parameter*
  flows into a donated position poisons its callers' arguments too
  (resolved through the package call graph, ambiguity → silence).

The donating call itself is exempt (``state = fn(state, batch)``
reads then rebinds ``state`` — the idiom the API wants), as is any
path where the name is rebound before the read.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

from ray_tpu._private.lint._ast_util import call_name, dotted, literal
from ray_tpu._private.lint.callgraph import (
    CallGraph, FuncInfo, get_call_graph,
)
from ray_tpu._private.lint.core import (
    Finding, LintPass, ModuleInfo, register,
)
from ray_tpu._private.lint.dataflow import (
    bound_names, cfgs_for_module, deleted_names, effective_exprs, solve,
    walk_no_scope,
)

_JIT_TAILS = {"jit", "pjit", "tracked_jit"}


def donated_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Donated positions of a jit-family wrap call with a literal
    ``donate_argnums``, else None."""
    if call_name(call).rsplit(".", 1)[-1] not in _JIT_TAILS:
        return None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            val = literal(kw.value)
            if isinstance(val, int):
                return (val,)
            if isinstance(val, (tuple, list)) and val and all(
                    isinstance(v, int) for v in val):
                return tuple(val)
    return None


def _pure_dotted(expr: ast.expr) -> Optional[str]:
    """"a.b.c" for a Name/Attribute chain of plain names, else None."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        base = _pure_dotted(expr.value)
        return f"{base}.{expr.attr}" if base else None
    return None


class _ModuleMaps:
    """Where donating callables live in one module: per-scope names and
    per-class ``self.X`` attributes."""

    def __init__(self, mod: ModuleInfo):
        # scope key: id(enclosing function node), or None at module level
        self.scoped: Dict[Optional[int], Dict[str, Tuple[int, ...]]] = {}
        self.class_attr: Dict[str, Dict[str, Tuple[int, ...]]] = {}
        self._index(mod.tree, None, "")

    def _index(self, node: ast.AST, scope: Optional[int],
               cls: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                self._index(child, scope, child.name)
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                self._index(child, id(child), cls)
            else:
                if isinstance(child, ast.Assign) and isinstance(
                        child.value, ast.Call):
                    pos = donated_positions(child.value)
                    if pos is not None:
                        self._record(child.targets, pos, scope, cls)
                self._index(child, scope, cls)

    def _record(self, targets, pos, scope, cls) -> None:
        for t in targets:
            if isinstance(t, ast.Name):
                prev = self.scoped.setdefault(scope, {}).get(t.id, ())
                self.scoped[scope][t.id] = tuple(sorted(set(prev)
                                                        | set(pos)))
            elif isinstance(t, ast.Attribute) and isinstance(
                    t.value, ast.Name) and t.value.id == "self" and cls:
                attrs = self.class_attr.setdefault(cls, {})
                prev = attrs.get(t.attr, ())
                attrs[t.attr] = tuple(sorted(set(prev) | set(pos)))


@register
class DonationPass(LintPass):
    name = "donation-use-after"
    rules = ("donation-use-after",)
    description = ("no reads of a binding after it flowed into a "
                   "donate_argnums position on some path: donated HBM "
                   "is XLA's to reuse, so the read returns garbage on "
                   "a real TPU")

    def __init__(self):
        self._mods: List[ModuleInfo] = []

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        self._mods.append(mod)
        return ()

    def finalize(self) -> Iterable[Finding]:
        graph = get_call_graph(self._mods)
        maps = {m.relpath: _ModuleMaps(m) for m in self._mods}
        summaries = self._summaries(graph, maps)
        out: List[Finding] = []
        for mod in self._mods:
            if "donate_argnums" not in mod.src and not summaries:
                continue
            out.extend(self._check_module(mod, graph, maps, summaries))
        return out

    # ------------------------------------------------ callable lookup

    def _call_positions(self, call: ast.Call, fi: Optional[FuncInfo],
                        mod: ModuleInfo, graph: CallGraph,
                        maps: Dict[str, _ModuleMaps],
                        summaries: Dict[int, Set[int]],
                        ) -> List[Tuple[int, int]]:
        """(donated-position-in-callee, call-arg-index) pairs for this
        call site."""
        mm = maps[mod.relpath]
        func = call.func
        # jax.jit(f, donate_argnums=...)(args): wrap applied in place.
        if isinstance(func, ast.Call):
            pos = donated_positions(func)
            if pos is not None:
                return [(p, p) for p in pos]
        if isinstance(func, ast.Name):
            scope_chain: List[Optional[int]] = []
            f = fi
            while f is not None:
                scope_chain.append(id(f.node))
                f = f.parent
            scope_chain.append(None)
            for scope in scope_chain:
                pos = mm.scoped.get(scope, {}).get(func.id)
                if pos is not None:
                    return [(p, p) for p in pos]
        elif isinstance(func, ast.Attribute) and isinstance(
                func.value, ast.Name) and func.value.id in (
                    "self", "cls") and fi is not None and fi.cls:
            pos = mm.class_attr.get(fi.cls, {}).get(func.attr)
            if pos is not None:
                return [(p, p) for p in pos]
        # One-level summary through the call graph.
        callee = graph.resolve(func, fi, mod)
        if callee is not None and id(callee.node) in summaries:
            shift = 0
            if callee.cls and isinstance(func, ast.Attribute):
                params = callee.node.args.args
                if params and params[0].arg in ("self", "cls"):
                    shift = 1
            return [(p, p - shift)
                    for p in summaries[id(callee.node)]
                    if p - shift >= 0]
        return []

    def _summaries(self, graph: CallGraph,
                   maps: Dict[str, _ModuleMaps]) -> Dict[int, Set[int]]:
        """id(func node) → parameter indices the function donates
        (one level: param flows directly into a donated position of a
        locally-known donating callable)."""
        out: Dict[int, Set[int]] = {}
        for fi in graph.funcs:
            args = fi.node.args
            params = [a.arg for a in args.posonlyargs + args.args]
            if not params:
                continue
            for call, _callee in graph.direct_calls(fi):
                for pos, argidx in self._call_positions(
                        call, fi, fi.mod, graph, maps, {}):
                    if argidx >= len(call.args):
                        continue
                    arg = call.args[argidx]
                    if any(isinstance(a, ast.Starred)
                           for a in call.args[:argidx + 1]):
                        continue
                    if isinstance(arg, ast.Name) and arg.id in params:
                        out.setdefault(id(fi.node), set()).add(
                            params.index(arg.id))
        return out

    # -------------------------------------------------------- analysis

    def _check_module(self, mod: ModuleInfo, graph: CallGraph,
                      maps: Dict[str, _ModuleMaps],
                      summaries: Dict[int, Set[int]],
                      ) -> Iterable[Finding]:
        for fn, cfg in cfgs_for_module(mod).items():
            fi = graph.by_node.get(id(fn))
            yield from self._check_function(fn, cfg, fi, mod, graph,
                                            maps, summaries)

    def _check_function(self, fn, cfg, fi, mod, graph, maps,
                        summaries) -> Iterable[Finding]:
        State = Dict[str, FrozenSet[int]]     # dotted name → donation lines
        reported: Dict[Tuple[int, str, int], Tuple[ast.AST, str, int]] = {}

        def join(a: State, b: State) -> State:
            out = dict(a)
            for k, v in b.items():
                out[k] = out.get(k, frozenset()) | v
            return out

        def loads_of(stmt: ast.AST) -> List[Tuple[str, ast.AST]]:
            exprs = list(effective_exprs(stmt))
            if isinstance(stmt, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                targets = stmt.targets if isinstance(stmt, ast.Assign) \
                    else [stmt.target]
                # ``state.field = x`` / ``d[k] = x`` read their base.
                exprs += [t for t in targets
                          if not isinstance(t, ast.Name)]
            out: List[Tuple[str, ast.AST]] = []
            for e in exprs:
                for n in walk_no_scope(e):
                    if isinstance(n, ast.Name) and isinstance(
                            n.ctx, ast.Load):
                        out.append((n.id, n))
                    elif isinstance(n, ast.Attribute) and isinstance(
                            n.ctx, ast.Load):
                        d = _pure_dotted(n)
                        if d is not None:
                            out.append((d, n))
            if isinstance(stmt, ast.AugAssign):
                d = _pure_dotted(stmt.target)
                if d is not None:
                    out.append((d, stmt.target))
            return out

        def transfer(block, st: State) -> State:
            st = dict(st)
            for stmt in block.stmts:
                # 1. Reads checked against the incoming poison set.
                for name, node in loads_of(stmt):
                    for key, lines in st.items():
                        if name == key or name.startswith(key + "."):
                            for ln in lines:
                                rk = (getattr(node, "lineno", 0), key, ln)
                                reported.setdefault(rk, (node, key, ln))
                # 2. New donations from calls in this statement.
                for e in effective_exprs(stmt):
                    for n in walk_no_scope(e):
                        if not isinstance(n, ast.Call):
                            continue
                        for pos, argidx in self._call_positions(
                                n, fi, mod, graph, maps, summaries):
                            if argidx >= len(n.args):
                                continue
                            if any(isinstance(a, ast.Starred)
                                   for a in n.args[:argidx + 1]):
                                continue
                            d = _pure_dotted(n.args[argidx])
                            if d is not None:
                                st[d] = st.get(d, frozenset()) \
                                    | frozenset([n.lineno])
                # 3. Rebinds clear the poison.
                kills = set(bound_names(stmt)) | set(deleted_names(stmt))
                if isinstance(stmt, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign)):
                    targets = stmt.targets if isinstance(
                        stmt, ast.Assign) else [stmt.target]
                    flat: List[ast.expr] = []
                    while targets:
                        t = targets.pop()
                        if isinstance(t, (ast.Tuple, ast.List)):
                            targets.extend(t.elts)
                        elif isinstance(t, ast.Starred):
                            targets.append(t.value)
                        else:
                            flat.append(t)
                    for t in flat:
                        d = _pure_dotted(t)
                        if d is not None:
                            kills.add(d)
                if kills:
                    for key in list(st):
                        if key in kills or any(
                                key.startswith(k + ".") for k in kills):
                            del st[key]
            return st

        solve(cfg, transfer, {}, join, follow_exc=False)
        for node, key, donate_line in reported.values():
            yield mod.finding(
                "donation-use-after", node,
                f"'{key}' is read in {fn.name}() after flowing into a "
                f"donate_argnums position at line {donate_line}: the "
                f"buffer belongs to XLA once donated and may already "
                f"be reused, so this read returns garbage on TPU — "
                f"rebind the name from the call's result or drop the "
                f"donation")
