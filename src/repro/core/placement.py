"""Tier enforcement for real JAX arrays via memory-kind shardings.

An arena is a named group of ``jax.Array``s (e.g. one layer's optimizer
moments).  Enforcement remaps the group between the fast tier
(``memory_kind="device"`` — HBM on TPU) and the slow tier
(``memory_kind="pinned_host"`` — host DRAM) with ``jax.device_put``; the
partition spec is never changed, only the memory kind, so migration composes
with any DP/TP/EP sharding.

Fractional assignments are realized at array granularity: the hottest-first
stable order of the arena's entries keeps a prefix on the fast tier whose
byte count best matches the recommended fraction.  (Paged pools — KV caches —
do better: they migrate at page granularity inside ``serve/kvcache.py``.)

The trainer-facing helpers ``fetch_fast``/``current`` implement the offload
execution model: compute always runs on device-kind arrays; slow-tier arenas
pay an explicit per-step transfer, which is precisely the recurring "rental"
cost in the ski-rental model.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax

from .arenas import ArenaManager
from .runtime import FractionPlacer


def _with_memory_kind(x: jax.Array, kind: str) -> jax.Array:
    if x.sharding.memory_kind == kind:
        return x
    return jax.device_put(x, x.sharding.with_memory_kind(kind))


def memory_kind_of(x: jax.Array) -> Optional[str]:
    return getattr(x.sharding, "memory_kind", None)


@dataclasses.dataclass
class ArrayEntry:
    name: str
    array: jax.Array

    @property
    def nbytes(self) -> int:
        return int(self.array.size * self.array.dtype.itemsize)


class JaxArenaPlacer(FractionPlacer):
    """FractionPlacer whose ``_apply`` migrates real arrays between tiers."""

    def __init__(
        self,
        arenas: ArenaManager,
        fast_kind: str = "device",
        slow_kind: str = "pinned_host",
    ):
        super().__init__(arenas)
        self.fast_kind = fast_kind
        self.slow_kind = slow_kind
        self._store: Dict[int, List[ArrayEntry]] = {}
        self.transfers_bytes: int = 0  # telemetry: total bytes device_put moved

    # ----------------------------------------------------------------- store
    def bind(self, arena_id: int, name: str, array: jax.Array) -> None:
        """Register an array; it is immediately placed according to the
        arena's current fast fraction (first-touch placement happens in the
        ArenaManager, the placer realizes it physically)."""
        entries = self._store.setdefault(arena_id, [])
        for e in entries:
            if e.name == name:
                e.array = array
                break
        else:
            entries.append(ArrayEntry(name=name, array=array))
        arena = self.arenas.arena_by_id(arena_id)
        if arena is not None and arena.fast_fraction < 1.0:
            self._apply(arena_id, arena.fast_fraction)

    def bind_tree(self, arena_id: int, tree: Any, prefix: str = "") -> None:
        leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, leaf in leaves:
            self.bind(arena_id, prefix + jax.tree_util.keystr(path), leaf)

    def entries(self, arena_id: int) -> List[ArrayEntry]:
        return self._store.get(arena_id, [])

    def get(self, arena_id: int, name: str) -> jax.Array:
        for e in self._store.get(arena_id, []):
            if e.name == name:
                return e.array
        raise KeyError(f"arena {arena_id} has no array {name!r}")

    # ----------------------------------------------------------- enforcement
    def _apply(self, arena_id: int, new_fraction: float) -> None:
        entries = self._store.get(arena_id)
        if not entries:
            return
        total = sum(e.nbytes for e in entries)
        budget = int(round(new_fraction * total))
        for e in entries:  # stable order: prefix goes fast
            target = self.fast_kind if budget >= e.nbytes else self.slow_kind
            if budget >= e.nbytes:
                budget -= e.nbytes
            if memory_kind_of(e.array) != target:
                self.transfers_bytes += e.nbytes
                e.array = _with_memory_kind(e.array, target)

    # --------------------------------------------------------- step interface
    def fetch_fast(self, arena_id: int) -> Dict[str, jax.Array]:
        """Device-kind copies of the arena for compute.  Slow-tier entries pay
        a transfer (the rental); fast-tier entries are returned as-is."""
        out: Dict[str, jax.Array] = {}
        for e in self._store.get(arena_id, []):
            if memory_kind_of(e.array) == self.fast_kind:
                out[e.name] = e.array
            else:
                self.transfers_bytes += e.nbytes
                out[e.name] = _with_memory_kind(e.array, self.fast_kind)
        return out

    def writeback(self, arena_id: int, values: Dict[str, jax.Array]) -> None:
        """Store updated values, preserving each entry's current tier."""
        for e in self._store.get(arena_id, []):
            if e.name not in values:
                continue
            new = values[e.name]
            kind = memory_kind_of(e.array)
            if kind == self.slow_kind:
                self.transfers_bytes += e.nbytes
                new = _with_memory_kind(new, self.slow_kind)
            e.array = new

    def fast_bytes(self) -> int:
        return sum(
            e.nbytes
            for entries in self._store.values()
            for e in entries
            if memory_kind_of(e.array) == self.fast_kind
        )

    def slow_bytes(self) -> int:
        return sum(
            e.nbytes
            for entries in self._store.values()
            for e in entries
            if memory_kind_of(e.array) != self.fast_kind
        )
