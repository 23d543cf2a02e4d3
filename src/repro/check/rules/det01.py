"""DET01 — nondeterminism must not reach traced code.

Walks the module-local call graph from every jit entry point (functions
decorated with / passed to ``jax.jit``, ``pl.pallas_call``,
``shard_map``, ``jax.pmap``) and flags, anywhere reachable:

  * stdlib ``random.*`` and ``np.random.*`` calls — their values bake
    into the trace as constants that differ between traces (and between
    processes), silently breaking replay and cache hits;
  * wall-clock reads (``time.time``/``perf_counter``/``monotonic``,
    ``datetime.now``) — same trace-constant hazard;
  * iteration over a set literal / ``set()``/``frozenset()`` value —
    iteration order depends on PYTHONHASHSEED, so the traced program
    (op order, and with it numerics) differs run to run.

``jax.random.*`` with explicit keys is the sanctioned path and is not
flagged.  The walk is module-local by design: each module is analyzed
from its own entry points, and cross-module helpers are covered when
their defining module is swept.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional

from .. import callgraph
from ..registry import Module, Rule, register
from ..report import Finding

_JIT_ENTRY_SUFFIXES = ("jax.jit", "pallas.pallas_call", "pl.pallas_call",
                       "jax.pmap")
_SHARD_MAP_NAMES = ("shard_map", "shard_map_unchecked")

_TIME_CALLS = {
    "time.time", "time.time_ns", "time.perf_counter",
    "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
    "datetime.datetime.now", "datetime.datetime.utcnow", "datetime.now",
    "datetime.utcnow",
}


def _is_tracing_transform(qn: Optional[str]) -> bool:
    if qn is None:
        return False
    if qn.endswith(_JIT_ENTRY_SUFFIXES):
        return True
    return qn.split(".")[-1] in _SHARD_MAP_NAMES


def _callee_expr(node: ast.expr, module: Module) -> Optional[ast.AST]:
    """Resolve the traced function from a transform's argument: a bare
    local name, a lambda, or ``functools.partial(name, ...)``."""
    if isinstance(node, ast.Lambda):
        return node
    if isinstance(node, ast.Name):
        return module.functions.get(node.id)
    if isinstance(node, ast.Call):
        qn = module.imports.qualname(node.func)
        if qn is not None and qn.split(".")[-1] == "partial" and node.args:
            return _callee_expr(node.args[0], module)
    return None


def _entry_points(module: Module) -> List[ast.AST]:
    entries: List[ast.AST] = []
    for fn in module.functions.values():
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            qn = module.imports.qualname(target)
            if _is_tracing_transform(qn):
                entries.append(fn)
            elif (isinstance(dec, ast.Call)
                    and qn is not None and qn.split(".")[-1] == "partial"
                    and dec.args
                    and _is_tracing_transform(
                        module.imports.qualname(dec.args[0]))):
                entries.append(fn)
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call) and \
                _is_tracing_transform(module.imports.qualname(node.func)):
            if node.args:
                body = _callee_expr(node.args[0], module)
                if body is not None:
                    entries.append(body)
    return entries


def _set_valued(node: ast.expr) -> bool:
    if isinstance(node, ast.Set):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id in ("set", "frozenset")
    return False


@register
class Det01(Rule):
    id = "DET01"
    title = ("nondeterministic source (random/np.random/clock/set "
             "iteration) reachable from a jit/pallas/shard_map entry")

    def check(self, module: Module) -> Iterator[Finding]:
        entries = _entry_points(module)
        if not entries:
            return
        seen = set()
        for fn in callgraph.reachable(entries, module.functions):
            where = getattr(fn, "name", "<lambda>")
            for node in ast.walk(fn):
                if id(node) in seen:
                    continue
                seen.add(id(node))
                if isinstance(node, ast.Call):
                    qn = module.imports.qualname(node.func)
                    if qn is None:
                        continue
                    if qn.startswith("random.") or \
                            qn.startswith("numpy.random."):
                        yield module.finding(
                            node, self.id,
                            f"'{qn}' inside traced code (via '{where}') "
                            f"bakes a different constant into every "
                            f"trace — use jax.random with an explicit "
                            f"key")
                    elif qn in _TIME_CALLS:
                        yield module.finding(
                            node, self.id,
                            f"wall-clock read '{qn}' inside traced code "
                            f"(via '{where}') is a trace-time constant — "
                            f"hoist it out of the jitted region")
                elif isinstance(node, (ast.For, ast.comprehension)):
                    it = node.iter
                    if _set_valued(it):
                        yield module.finding(
                            it, self.id,
                            f"iteration over a set inside traced code "
                            f"(via '{where}') depends on PYTHONHASHSEED "
                            f"— sort it or use a list/tuple")
