"""SPMD01 — shard_map axis-name hygiene and ppermute perm coverage.

Two checks, both resolution-gated (an unresolvable site is skipped, not
guessed at):

* **Axis binding** — inside a function handed to ``shard_map`` /
  ``shard_map_unchecked``, every ``jax.lax`` collective must name an axis
  the enclosing mesh actually binds.  Bound names are recovered from the
  ``PartitionSpec``/``P`` literals in the call's in/out specs (following
  one level of local assignment) and from an inline ``Mesh(...,
  axis_names=...)``; the walk follows module-local helpers.  An unbound
  name fails at runtime only when that code path is first traced on a
  real mesh — exactly the kind of latent break the ep dispatch hit.

* **Perm coverage** — a ``ppermute`` perm given as a literal list must
  be a permutation: duplicate sources would send two payloads into one
  receive buffer, duplicate destinations drop data, and a gap in
  ``0..max(src)`` silently zero-fills a shard.  The rotation idiom
  ``[(j, (j ± k) % n) for j in range(n)]`` is recognized as covering.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set

from .. import callgraph
from ..registry import Module, Rule, register
from ..report import Finding

_SHARD_MAP_NAMES = {"shard_map", "shard_map_unchecked"}
# jax.lax collective -> index of its axis-name argument.
_COLLECTIVES = {"psum": 1, "pmean": 1, "pmax": 1, "pmin": 1,
                "all_gather": 1, "all_to_all": 1, "ppermute": 1,
                "psum_scatter": 1, "pshuffle": 1, "axis_index": 0}


def _last_name(node: ast.expr) -> str:
    while isinstance(node, ast.Attribute):
        return node.attr
    return node.id if isinstance(node, ast.Name) else ""


def _kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _axis_strings(expr: Optional[ast.expr], module: Module,
                  *scopes) -> Optional[Set[str]]:
    """Axis names bound by a specs expression.  None = unresolvable."""
    if expr is None:
        return None
    if isinstance(expr, ast.Name):
        expr = callgraph.resolve_assignment(expr.id, *scopes)
        if expr is None:
            return None
    axes: Set[str] = set()
    resolvable = True
    for node in ast.walk(expr):
        if isinstance(node, ast.Call) and \
                _last_name(node.func) in ("P", "PartitionSpec"):
            for arg in node.args:
                found = _axis_value(arg, *scopes)
                if found is None:
                    resolvable = False
                else:
                    axes.update(found)
    return axes if resolvable else None


def _axis_value(arg: ast.expr, *scopes) -> Optional[Set[str]]:
    """Strings named by one PartitionSpec entry; None = unresolvable."""
    if isinstance(arg, ast.Constant):
        if arg.value is None:
            return set()
        return {arg.value} if isinstance(arg.value, str) else None
    if isinstance(arg, ast.Tuple):
        out: Set[str] = set()
        for e in arg.elts:
            got = _axis_value(e, *scopes)
            if got is None:
                return None
            out.update(got)
        return out
    if isinstance(arg, ast.Name):
        s = callgraph.resolve_str(arg.id, *scopes)
        return {s} if s is not None else None
    return None


def _mesh_axes(call: ast.Call, *scopes) -> Set[str]:
    mesh = _kwarg(call, "mesh")
    if mesh is None and len(call.args) > 1:
        mesh = call.args[1]
    if isinstance(mesh, ast.Name):
        mesh = callgraph.resolve_assignment(mesh.id, *scopes)
    if isinstance(mesh, ast.Call) and _last_name(mesh.func) == "Mesh":
        names = _kwarg(mesh, "axis_names")
        if names is None and len(mesh.args) > 1:
            names = mesh.args[1]
        got = _axis_value(names, *scopes) if names is not None else None
        return got or set()
    return set()


def _rotation_comprehension(expr: ast.expr) -> bool:
    """[(j, f(j)) for j in range(n)] — covers every source once."""
    if not isinstance(expr, (ast.ListComp, ast.GeneratorExp)):
        return False
    if len(expr.generators) != 1:
        return False
    gen = expr.generators[0]
    over_range = (isinstance(gen.iter, ast.Call)
                  and _last_name(gen.iter.func) == "range")
    if not (over_range and isinstance(gen.target, ast.Name)):
        return False
    elt = expr.elt
    return (isinstance(elt, ast.Tuple) and len(elt.elts) == 2
            and isinstance(elt.elts[0], ast.Name)
            and elt.elts[0].id == gen.target.id)


@register
class Spmd01(Rule):
    id = "SPMD01"
    title = ("collective inside shard_map names an axis the mesh does "
             "not bind, or a ppermute perm with duplicate/missing "
             "sources")

    def check(self, module: Module) -> Iterator[Finding]:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and \
                    _last_name(node.func) in _SHARD_MAP_NAMES:
                yield from self._check_site(module, node)
        # Perm validity matters wherever a ppermute appears, shard_map
        # context or not (helper functions are used from inside one).
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) and \
                    _last_name(node.func) == "ppermute":
                yield from self._check_perm(module, node)

    def _check_site(self, module: Module,
                    call: ast.Call) -> Iterator[Finding]:
        scope = callgraph.enclosing(
            call, module.parents, (ast.FunctionDef, ast.AsyncFunctionDef))
        scopes = (scope, module.tree)
        bound: Set[str] = set()
        known = True
        for kw in ("in_specs", "out_specs"):
            axes = _axis_strings(_kwarg(call, kw), module, *scopes)
            if axes is None:
                known = False
            else:
                bound |= axes
        # shard_map_unchecked(f, mesh, in_specs, out_specs) is positional.
        for pos in (2, 3):
            if len(call.args) > pos:
                axes = _axis_strings(call.args[pos], module, *scopes)
                if axes is None:
                    known = False
                else:
                    bound |= axes
        bound |= _mesh_axes(call, *scopes)
        if not known or not bound:
            return                     # unresolvable site: stay silent
        body = self._body_fn(module, call)
        if body is None:
            return
        for fn in callgraph.reachable([body], module.functions):
            for node in ast.walk(fn):
                axis = self._collective_axis(module, node, scopes)
                if axis is not None and axis not in bound:
                    yield module.finding(
                        node, self.id,
                        f"collective uses axis '{axis}' but the "
                        f"enclosing shard_map binds only "
                        f"{sorted(bound)} — an unbound name fails at "
                        f"trace time on a real mesh")

    def _body_fn(self, module: Module, call: ast.Call):
        if not call.args:
            return None
        arg = call.args[0]
        if isinstance(arg, ast.Lambda):
            return arg
        if isinstance(arg, ast.Name):
            return module.functions.get(arg.id)
        return None

    def _collective_axis(self, module: Module, node: ast.AST,
                         scopes) -> Optional[str]:
        if not isinstance(node, ast.Call):
            return None
        name = _last_name(node.func)
        if name not in _COLLECTIVES:
            return None
        qn = module.imports.qualname(node.func)
        if qn is not None and "lax" not in qn and not qn.startswith("jax."):
            return None                # some other psum/all_gather
        axis = _kwarg(node, "axis_name")
        if axis is None:
            idx = _COLLECTIVES[name]
            if len(node.args) > idx:
                axis = node.args[idx]
        if isinstance(axis, ast.Constant) and isinstance(axis.value, str):
            return axis.value
        if isinstance(axis, ast.Name):
            return callgraph.resolve_str(axis.id, *scopes)
        return None

    def _check_perm(self, module: Module,
                    call: ast.Call) -> Iterator[Finding]:
        perm = _kwarg(call, "perm")
        if perm is None and len(call.args) > 2:
            perm = call.args[2]
        if isinstance(perm, ast.Name):
            scope = callgraph.enclosing(
                call, module.parents,
                (ast.FunctionDef, ast.AsyncFunctionDef))
            perm = callgraph.resolve_assignment(
                perm.id, scope, module.tree)
        if perm is None or _rotation_comprehension(perm):
            return
        if not isinstance(perm, (ast.List, ast.Tuple)):
            return
        pairs: List[tuple] = []
        for e in perm.elts:
            if not (isinstance(e, ast.Tuple) and len(e.elts) == 2
                    and all(isinstance(x, ast.Constant)
                            and isinstance(x.value, int)
                            for x in e.elts)):
                return                 # not fully literal: stay silent
            pairs.append((e.elts[0].value, e.elts[1].value))
        if not pairs:
            return
        srcs = [s for s, _ in pairs]
        dsts = [d for _, d in pairs]
        if len(set(srcs)) != len(srcs):
            yield module.finding(
                call, self.id,
                f"ppermute perm has duplicate sources "
                f"{sorted(s for s in set(srcs) if srcs.count(s) > 1)} — "
                f"two payloads race into one receive buffer")
            return
        if len(set(dsts)) != len(dsts):
            yield module.finding(
                call, self.id,
                f"ppermute perm has duplicate destinations "
                f"{sorted(d for d in set(dsts) if dsts.count(d) > 1)} — "
                f"one shard receives twice, another's data is dropped")
            return
        missing = sorted(set(range(max(srcs) + 1)) - set(srcs))
        if missing:
            yield module.finding(
                call, self.id,
                f"ppermute perm covers sources {sorted(set(srcs))} but "
                f"skips {missing} — uncovered shards receive zeros on "
                f"the axis")
