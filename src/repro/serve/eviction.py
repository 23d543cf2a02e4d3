"""First-class eviction policies for the paged KV pool.

Between guidance intervals the engine sometimes needs a free HBM slot *now*
(a paused session resumes, a new page is allocated).  Which resident page
loses its slot is a policy decision, previously inlined in the engine;
policies are now objects in a registry so serving benchmarks — and future
policies — select them by name.

``guided`` consults the latest enforced placement from the
``GuidanceRuntime`` (pages the last plan wanted fast never lose to pages it
wanted slow), tie-breaking by least-recently-scheduled request.  ``lru`` and
``fifo`` are the unguided baselines.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Type

from .kvcache import Page


class EvictionPolicy:
    """Picks the page that loses its HBM slot.  Stateless by default."""

    name = "base"

    def pick(self, candidates: List[Page], engine) -> Optional[int]:
        raise NotImplementedError

    def pick_many(self, candidates: List[Page], engine,
                  n: int) -> List[int]:
        """Pick up to ``n`` victims (ranked by repeated ``pick``); the
        engine swaps them out in ONE batched migration rather than one
        transfer per victim.  Policies with a cheaper bulk ranking may
        override."""
        pool = list(candidates)
        victims: List[int] = []
        while len(victims) < n and pool:
            vid = self.pick(pool, engine)
            if vid is None:
                break
            victims.append(vid)
            pool = [p for p in pool if p.page_id != vid]
        return victims


class LRUEviction(EvictionPolicy):
    """Evict the page of the least-recently-scheduled request.

    Refcount-aware: a page's recency is the MOST recent of its holders'
    last-scheduled steps (a shared prefix page is as hot as its hottest
    request), and a page whose only holder is the prefix cache falls back
    to the page's own ``last_used`` clock (its last hit/attach)."""

    name = "lru"

    @staticmethod
    def _recency(engine):
        """page -> the MOST recent last-scheduled step of its live holders,
        else its own ``last_used`` — one pass over the sequence table."""
        stamp: Dict[int, int] = {}
        for rid, req in engine.requests.items():
            for p in engine.pool.request_pages(rid):
                stamp[p.page_id] = max(stamp.get(p.page_id, req.last_scheduled),
                                       req.last_scheduled)
        return lambda p: stamp.get(p.page_id, p.last_used)

    def pick(self, candidates: List[Page], engine) -> Optional[int]:
        if not candidates:
            return None
        return min(candidates, key=self._recency(engine)).page_id

    def pick_many(self, candidates: List[Page], engine,
                  n: int) -> List[int]:
        """Repeated ``pick`` is a stable sort on the same key (``min``
        keeps the first of equals): rank once."""
        ranked = sorted(candidates, key=self._recency(engine))
        return [p.page_id for p in ranked[:n]]


class FIFOEviction(EvictionPolicy):
    """Evict the oldest page by birth step."""

    name = "fifo"

    def pick(self, candidates: List[Page], engine) -> Optional[int]:
        if not candidates:
            return None
        return min(candidates, key=lambda p: p.birth_step).page_id


class GuidedEviction(LRUEviction):
    """Prefer pages the last recommendation placed on the slow tier; fall
    back to LRU among equals (and entirely, before the first interval)."""

    name = "gdt"

    def pick(self, candidates: List[Page], engine) -> Optional[int]:
        recs: Dict[int, bool] = getattr(engine, "last_recs", {}) or {}
        if recs:
            cold = [p for p in candidates if not recs.get(p.page_id, False)]
            if cold:
                candidates = cold
        return super().pick(candidates, engine)

    def pick_many(self, candidates: List[Page], engine,
                  n: int) -> List[int]:
        """Repeated ``pick`` takes every cold page (LRU among them) before
        any page the plan wanted fast: one stable sort on (wanted fast,
        recency)."""
        recs: Dict[int, bool] = getattr(engine, "last_recs", {}) or {}
        recency = self._recency(engine)
        ranked = sorted(candidates, key=lambda p: (
            bool(recs.get(p.page_id, False)), recency(p)))
        return [p.page_id for p in ranked[:n]]


EVICTION_POLICIES: Dict[str, Type[EvictionPolicy]] = {}


def register_eviction_policy(cls: Type[EvictionPolicy]) -> Type[EvictionPolicy]:
    EVICTION_POLICIES[cls.name] = cls
    return cls


for _cls in (LRUEviction, FIFOEviction, GuidedEviction):
    register_eviction_policy(_cls)


def make_eviction_policy(name: str) -> EvictionPolicy:
    try:
        return EVICTION_POLICIES[name]()
    except KeyError:
        raise ValueError(
            f"unknown eviction policy {name!r}; "
            f"expected one of {sorted(EVICTION_POLICIES)}") from None
