"""Paged KV cache with two memory tiers — the serving-side realization of
the paper's arenas.

Layout: one global pool of pages per tier; a page holds ``page_size`` tokens
of K and V for *all* layers: (L, page_size, K, dh).  Pages migrate between
the HBM pool (memory kind "device") and the host tier ("pinned_host"
slabs, ``serve/tier_io.HostTier``) as whole blocks — they are the
``ChunkStats`` chunks the fragmentation engine reasons about, and each
*request* is an allocation site whose arena is its page list.

Attention computes only against the HBM pool; a page on the host tier must
be swapped in before its sequence can decode (the swap is the rental the
ski-rental controller weighs).  The engine keeps exact per-page access
counts — on every decode step the access set is known statically (all pages
of the scheduled sequences, or the window's pages under SWA).

Migrations are batched: ``swap_in_many`` / ``swap_out_many`` realize a whole
direction of a ``MigrationPlan`` as one batched transfer per pool array
(``serve/tier_io.py``), so enforcing an N-page plan costs a constant number
of host<->device transfers (``transfer_events`` is the probe) while the
per-page swap/byte counters stay exact.

Pages are REFCOUNTED, not single-owner: the cross-request prefix cache
(serve/prefix_cache.py) shares one physical page between every request whose
prompt starts with the same token blocks, plus one reference held by the
cache itself.  ``Page.request_id`` is provenance only (the allocator);
authoritative request->pages association lives in the pool's per-request
sequence table (``request_pages``/``attach``/``release_request``), and
``free`` is a refcount decrement that releases physical slots only at zero.
Shared pages are immutable (copy-on-write: ``copy_page`` gives a writer a
private copy) — sharing is full-page granular, so the serving engine never
writes into a page with refcount > 1 on the normal path.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .tier_io import HostTier, copy_rows, gather_rows


@dataclasses.dataclass
class PageExport:
    """KV pages staged out of one pool for import into another — the warm
    half of live request migration.  Data is host-side numpy (one batched
    gather per source tier), bitwise-exact: the importing pool lands the
    same bytes it would have computed itself.  ``fast`` preserves the
    source pool's tier placement so the guidance state (where Algorithm 1
    put each page) survives the membership change when the destination has
    room."""

    page_ids: List[int]
    index_in_seq: List[int]
    tokens_used: List[int]
    accesses: List[float]
    fast: List[bool]              # source-tier residency (True = HBM)
    k: np.ndarray                 # (L, n, P, K, dh)
    v: np.ndarray
    n_layers: int
    shape: Tuple[int, ...]        # (page_size, kv_heads, head_dim)

    def __len__(self) -> int:
        return len(self.page_ids)

    def select_from(self, first_index: int) -> "PageExport":
        """Sub-export of the pages at ``index_in_seq >= first_index`` —
        what remains to import after the destination's prefix cache already
        covered the leading blocks."""
        rows = [i for i, idx in enumerate(self.index_in_seq)
                if idx >= first_index]
        return PageExport(
            page_ids=[self.page_ids[i] for i in rows],
            index_in_seq=[self.index_in_seq[i] for i in rows],
            tokens_used=[self.tokens_used[i] for i in rows],
            accesses=[self.accesses[i] for i in rows],
            fast=[self.fast[i] for i in rows],
            k=self.k[:, rows], v=self.v[:, rows],
            n_layers=self.n_layers, shape=self.shape)


@dataclasses.dataclass
class Page:
    page_id: int                 # global logical id
    request_id: int              # ALLOCATOR provenance, not ownership: the
    #                              pool's sequence table is authoritative
    index_in_seq: int            # page number within the sequence
    birth_step: int
    hbm_slot: Optional[int]      # slot in HBM pool, None if on host
    host_slot: Optional[int]
    # Float, not int: ReweightProfile decays counters every interval, and
    # int-flooring ``1 * 0.5`` to zero would erase exactly the recency
    # signal decay is meant to preserve.
    accesses: float = 0.0
    tokens_used: int = 0
    # Lifecycle: one reference per attached request plus one for the prefix
    # cache when the page is a shared-prefix block.  ``free`` decrements;
    # physical slots release only at zero.
    refcount: int = 1
    # True once the prefix cache holds a reference — such pages are profiled
    # and tier-placed by the PrefixBackend, not the per-request KV backend.
    shared: bool = False
    # Step of the last attach/access — the eviction fallback clock for pages
    # whose only holder is the cache (no live request to LRU against).
    last_used: int = 0


class PagedKVPool:
    """Two-tier physical page pools + logical page bookkeeping.

    The HBM tier is one ``(L, hbm_pages, P, K, dh)`` array per K and V on
    ``device``; the host tier (``host``, a ``tier_io.HostTier``) parks
    pages in pinned_host slabs, one slab per request per migration batch,
    addressed by host slot.
    """

    def __init__(self, n_layers: int, page_size: int, kv_heads: int,
                 head_dim: int, hbm_pages: int, host_pages: int,
                 dtype=jnp.bfloat16, device=None):
        self.shape = (page_size, kv_heads, head_dim)
        self.page_size = page_size
        self.n_layers = n_layers
        self.device = device if device is not None else jax.devices()[0]
        self.host = HostTier(self.device, axis=1)
        pool_shape = (n_layers, hbm_pages) + self.shape
        dev = self.host.device_sharding
        self.k_hbm = jnp.zeros(pool_shape, dtype, device=dev)
        self.v_hbm = jnp.zeros(pool_shape, dtype, device=dev)

        self.hbm_pages = hbm_pages
        self.host_pages = host_pages
        self.free_hbm: List[int] = list(range(hbm_pages))
        self.free_host: List[int] = list(range(host_pages))
        self.pages: Dict[int, Page] = {}
        # request_id -> ordered page list (the authoritative association;
        # a shared page appears in every attached request's list).
        self._seq: Dict[int, List[Page]] = {}
        self._next_id = 0
        self.swaps_in = 0
        self.swaps_out = 0
        self.bytes_moved = 0
        # Host<->device transfer probe: one event per batched pool-array
        # transfer (K and V count separately).  A batched N-page migration
        # costs a constant number of events per direction; the per-page
        # path costs 2 per page.  The migration-parity test asserts on it.
        self.transfer_events = 0
        # Cross-pool live-migration counters — separate from the swap
        # counters: an export/import is replica handoff, not tier traffic.
        self.exported_pages = 0
        self.imported_pages = 0

    # ------------------------------------------------------------ alloc
    @property
    def page_bytes(self) -> int:
        n = self.n_layers
        for s in self.shape:
            n *= s
        return 2 * n * self.k_hbm.dtype.itemsize  # K and V

    def allocate(self, request_id: int, index_in_seq: int,
                 step: int) -> Page:
        if not self.free_hbm:
            raise MemoryError(
                f"HBM pool exhausted: all {self.hbm_pages} pages "
                f"(ServeConfig.hbm_pages) hold live or cached KV; evict or "
                f"free pages first, or raise ServeConfig.hbm_pages")
        slot = self.free_hbm.pop()
        page = Page(page_id=self._next_id, request_id=request_id,
                    index_in_seq=index_in_seq, birth_step=step,
                    hbm_slot=slot, host_slot=None, last_used=step)
        self._next_id += 1
        self.pages[page.page_id] = page
        self._seq.setdefault(request_id, []).append(page)
        return page

    def free(self, page_id: int):
        """Drop ONE reference; physical slots release only at refcount zero.
        Unknown or already-freed ids raise a named error — a double free
        under sharing would hand the same physical slot to two sequences."""
        page = self.pages.get(page_id)
        if page is None:
            raise ValueError(
                f"cannot free page {page_id}: unknown or already-freed id "
                f"(a page dies when its refcount reaches zero — freeing it "
                f"again, or freeing an id this pool never allocated, is a "
                f"lifecycle bug in the caller)")
        page.refcount -= 1
        if page.refcount > 0:
            return
        self.pages.pop(page_id)
        if page.hbm_slot is not None:
            self.free_hbm.append(page.hbm_slot)
        if page.host_slot is not None:
            self._release_host([page.host_slot])

    # ----------------------------------------------------------- sharing
    def acquire(self, page_id: int, shared: bool = False) -> Page:
        """Add one bare reference (the prefix cache's hold on a block).
        ``shared=True`` marks the page as cache-governed for profiling."""
        page = self.pages[page_id]
        page.refcount += 1
        if shared:
            page.shared = True
        return page

    def attach(self, request_id: int, page_id: int, step: int) -> Page:
        """Reference an existing (shared) page from ``request_id``'s
        sequence.  Pages attach in index order — prefix sharing is only
        legal over a sequence's leading full pages."""
        page = self.pages[page_id]
        seq = self._seq.setdefault(request_id, [])
        if len(seq) != page.index_in_seq:
            raise ValueError(
                f"cannot attach page {page_id} (index_in_seq="
                f"{page.index_in_seq}) to request {request_id} holding "
                f"{len(seq)} pages: prefix pages attach in order")
        page.refcount += 1
        page.last_used = step
        seq.append(page)
        return page

    def release_request(self, request_id: int) -> List[int]:
        """Drop every reference ``request_id`` holds.  Returns the ids of
        pages that actually died (shared pages survive on the cache's
        reference)."""
        freed: List[int] = []
        for page in self._seq.pop(request_id, []):
            self.free(page.page_id)
            if page.page_id not in self.pages:
                freed.append(page.page_id)
        return freed

    def holders(self, page_id: int) -> List[int]:
        """Request ids currently referencing a page (provenance-free)."""
        return [rid for rid, seq in self._seq.items()
                if any(p.page_id == page_id for p in seq)]

    def copy_page(self, page_id: int, request_id: int, step: int) -> Page:
        """Copy-on-write: give ``request_id`` a private HBM copy of a shared
        page, swapping it into the request's sequence in place.  The source
        must be HBM-resident (writers only ever target resident pages)."""
        src = self.pages[page_id]
        if src.hbm_slot is None:
            raise ValueError(
                f"cannot copy-on-write page {page_id}: not HBM-resident "
                f"(swap it in first)")
        if not self.free_hbm:
            raise MemoryError(
                f"HBM pool exhausted: all {self.hbm_pages} pages "
                f"(ServeConfig.hbm_pages) hold live or cached KV; evict or "
                f"free pages first, or raise ServeConfig.hbm_pages")
        seq = self._seq.get(request_id, [])
        at = next((i for i, p in enumerate(seq) if p.page_id == page_id),
                  None)
        if at is None:
            raise ValueError(
                f"cannot copy-on-write page {page_id}: request "
                f"{request_id} does not reference it")
        slot = self.free_hbm.pop()
        new = Page(page_id=self._next_id, request_id=request_id,
                   index_in_seq=src.index_in_seq, birth_step=step,
                   hbm_slot=slot, host_slot=None, accesses=src.accesses,
                   tokens_used=src.tokens_used, last_used=step)
        self._next_id += 1
        self.pages[new.page_id] = new
        self.k_hbm, self.v_hbm = copy_rows(
            (self.k_hbm, self.v_hbm), [slot],
            [gather_rows((self.k_hbm, self.v_hbm), [src.hbm_slot], axis=1)],
            [(0, 0)], axis=1)
        seq[at] = new
        self.free(page_id)               # drop the request's old reference
        return new

    # ------------------------------------------------------- migrations
    def _park(self, pages: Sequence["Page"], host_slots: Sequence[int]):
        """HBM -> host: one slab per allocating request (its pages travel
        together), ONE batched transfer per pool array for all of them.
        Reads the pages' current HBM slots, so call it before those slots
        are written again."""
        groups: Dict[int, List[Tuple[int, int]]] = {}
        for page, slot in zip(pages, host_slots):
            groups.setdefault(page.request_id, []).append(
                (page.hbm_slot, slot))
        self.host.park([
            ([s for _, s in pairs],
             gather_rows((self.k_hbm, self.v_hbm), [h for h, _ in pairs],
                         axis=1))
            for pairs in groups.values()])
        self.transfer_events += 2            # one per pool array (K, V)

    def _land(self, hbm_slots: Sequence[int], staged, locs) -> None:
        """Write staged rows (``HostTier.fetch`` output, or one numpy-fed
        slab) into HBM slots in place."""
        self.k_hbm, self.v_hbm = copy_rows(
            (self.k_hbm, self.v_hbm), hbm_slots, staged, locs, axis=1)

    def _unpark(self, host_slots: Sequence[int]):
        """host -> device: ONE batched transfer per pool array of the slabs
        holding the pages; ``_land`` writes them into HBM."""
        staged, locs = self.host.fetch(host_slots)
        self.transfer_events += 2
        return staged, locs

    def _release_host(self, slots: Sequence[int]) -> None:
        self.host.release(slots)
        self.free_host.extend(slots)

    def swap_out_many(self, page_ids: Sequence[int]):
        """HBM -> host, one batched transfer for the whole id list.
        Already-slow, unknown and duplicate ids are skipped; counters stay
        per-page exact (one swap + page_bytes per page actually moved)."""
        ids = [pid for pid in dict.fromkeys(page_ids)
               if pid in self.pages and self.pages[pid].hbm_slot is not None]
        if not ids:
            return
        if len(self.free_host) < len(ids):
            raise MemoryError("host pool exhausted")
        src = [self.pages[pid].hbm_slot for pid in ids]
        dst = [self.free_host.pop() for _ in ids]
        self._park([self.pages[pid] for pid in ids], dst)
        for pid, si, di in zip(ids, src, dst):
            page = self.pages[pid]
            self.free_hbm.append(si)
            page.hbm_slot, page.host_slot = None, di
        self.swaps_out += len(ids)
        self.bytes_moved += self.page_bytes * len(ids)

    def swap_in_many(self, page_ids: Sequence[int]):
        """host -> HBM, one batched transfer for the whole id list (unknown,
        already-fast and duplicate ids are skipped)."""
        ids = [pid for pid in dict.fromkeys(page_ids)
               if pid in self.pages and self.pages[pid].hbm_slot is None]
        if not ids:
            return
        if len(self.free_hbm) < len(ids):
            raise MemoryError(
                f"HBM pool exhausted: {len(ids)} pages to swap in but only "
                f"{len(self.free_hbm)} of {self.hbm_pages} slots "
                f"(ServeConfig.hbm_pages) are free; evict first or raise "
                f"ServeConfig.hbm_pages")
        src = [self.pages[pid].host_slot for pid in ids]
        dst = [self.free_hbm.pop() for _ in ids]
        self._land(dst, *self._unpark(src))
        self._release_host(src)
        for pid, di in zip(ids, dst):
            page = self.pages[pid]
            page.host_slot, page.hbm_slot = None, di
        self.swaps_in += len(ids)
        self.bytes_moved += self.page_bytes * len(ids)

    def exchange(self, out_ids: Sequence[int], in_ids: Sequence[int]):
        """Atomic bidirectional migration: demote ``out_ids`` and promote
        ``in_ids`` in one batched operation.

        Both directions are STAGED before any slot is freed, so the
        exchange succeeds even when both free lists are empty (a pure slot
        swap) — the case where evict-then-swap-in would deadlock on
        ``free_host``.  Feasibility: len(out) <= len(in) + free_host and
        len(in) <= len(out) + free_hbm (the scheduler's logical-page budget
        guarantees both).  Still one gather + one staged transfer + one
        scatter per pool array per direction.
        """
        outs = [pid for pid in dict.fromkeys(out_ids)
                if pid in self.pages and self.pages[pid].hbm_slot is not None]
        ins = [pid for pid in dict.fromkeys(in_ids)
               if pid in self.pages and self.pages[pid].hbm_slot is None]
        if not outs and not ins:
            return
        if len(outs) > len(ins) + len(self.free_host):
            raise MemoryError(
                f"host pool exhausted: {len(outs)} demotions need more than "
                f"the {len(self.free_host)} free of {self.host_pages} host "
                f"slots (ServeConfig.host_pages) plus {len(ins)} freed by "
                f"promotions; raise ServeConfig.host_pages")
        if len(ins) > len(outs) + len(self.free_hbm):
            raise MemoryError(
                f"HBM pool exhausted: {len(ins)} promotions need more than "
                f"the {len(self.free_hbm)} free of {self.hbm_pages} HBM "
                f"slots (ServeConfig.hbm_pages) plus {len(outs)} freed by "
                f"demotions; evict first or raise ServeConfig.hbm_pages")
        out_src = [self.pages[pid].hbm_slot for pid in outs]
        in_src = [self.pages[pid].host_slot for pid in ins]
        # Stage BOTH directions before any scatter: a destination slot may
        # be a just-freed source slot of the opposite direction.
        if ins:
            in_stage = self._unpark(in_src)
        out_pages = [self.pages[pid] for pid in outs]
        self.free_hbm.extend(out_src)
        self._release_host(in_src)
        in_dst = [self.free_hbm.pop() for _ in ins]
        out_dst = [self.free_host.pop() for _ in outs]
        if outs:
            self._park(out_pages, out_dst)
        if ins:
            self._land(in_dst, *in_stage)
        for pid, di in zip(outs, out_dst):
            page = self.pages[pid]
            page.hbm_slot, page.host_slot = None, di
        for pid, di in zip(ins, in_dst):
            page = self.pages[pid]
            page.host_slot, page.hbm_slot = None, di
        self.swaps_out += len(outs)
        self.swaps_in += len(ins)
        self.bytes_moved += self.page_bytes * (len(outs) + len(ins))

    # ------------------------------------------------- cross-pool handoff
    def export_pages(self, page_ids: Sequence[int]) -> PageExport:
        """Stage pages out of this pool for import into another (warm live
        migration).  One gather per source tier regardless of page count;
        the source pool is left untouched — the exporter releases its
        references separately once the handoff lands."""
        ids = list(dict.fromkeys(page_ids))
        missing = [pid for pid in ids if pid not in self.pages]
        if missing:
            raise ValueError(
                f"cannot export pages {missing}: unknown or freed ids")
        pages = [self.pages[pid] for pid in ids]
        n = len(pages)
        k = np.zeros((self.n_layers, n) + self.shape, self.k_hbm.dtype)
        v = np.zeros_like(k)
        fast_rows = [i for i, p in enumerate(pages) if p.hbm_slot is not None]
        slow_rows = [i for i, p in enumerate(pages) if p.hbm_slot is None]
        if fast_rows:
            kf, vf = jax.device_get(gather_rows(
                (self.k_hbm, self.v_hbm),
                [pages[i].hbm_slot for i in fast_rows], axis=1))
            k[:, fast_rows] = kf[:, :len(fast_rows)]
            v[:, fast_rows] = vf[:, :len(fast_rows)]
        if slow_rows:
            k_rows, v_rows = self.host.read(
                [pages[i].host_slot for i in slow_rows])
            k[:, slow_rows] = np.stack(k_rows, axis=1)
            v[:, slow_rows] = np.stack(v_rows, axis=1)
        self.exported_pages += n
        return PageExport(
            page_ids=[p.page_id for p in pages],
            index_in_seq=[p.index_in_seq for p in pages],
            tokens_used=[p.tokens_used for p in pages],
            accesses=[p.accesses for p in pages],
            fast=[p.hbm_slot is not None for p in pages],
            k=k, v=v, n_layers=self.n_layers, shape=self.shape)

    def import_pages(self, export: PageExport, request_id: int,
                     step: int) -> List[Page]:
        """Land an export into THIS pool as fresh private pages attached to
        ``request_id``.  Each page keeps its source tier when the matching
        free list has room (the exporter's guidance placement survives the
        handoff), overflows to the other tier otherwise, and the whole
        import raises ``MemoryError`` — before moving any data — when the
        pools combined cannot hold it (callers fall back to cold
        recompute).  One batched transfer per pool array per destination
        tier."""
        if (export.n_layers, tuple(export.shape)) != (self.n_layers,
                                                      tuple(self.shape)):
            raise ValueError(
                f"cannot import pages shaped {export.n_layers}x"
                f"{tuple(export.shape)} into a pool shaped "
                f"{self.n_layers}x{tuple(self.shape)}: replica engines "
                f"must share one model/page geometry")
        n = len(export)
        if n == 0:
            return []
        if n > len(self.free_hbm) + len(self.free_host):
            raise MemoryError(
                f"cannot import {n} pages: only {len(self.free_hbm)} free "
                f"HBM + {len(self.free_host)} free host slots on the "
                f"destination pool (hbm_pages={self.hbm_pages}, "
                f"host_pages={self.host_pages}); cold-migrate instead")
        room_fast, room_slow = len(self.free_hbm), len(self.free_host)
        fast_rows: List[int] = []
        slow_rows: List[int] = []
        for i in range(n):
            to_fast = export.fast[i] if (room_fast and room_slow) \
                else room_fast > 0
            if to_fast:
                fast_rows.append(i)
                room_fast -= 1
            else:
                slow_rows.append(i)
                room_slow -= 1
        new_pages: List[Optional[Page]] = [None] * n
        for rows, free, is_fast in ((fast_rows, self.free_hbm, True),
                                    (slow_rows, self.free_host, False)):
            if not rows:
                continue
            slots = [free.pop() for _ in rows]
            staged = (export.k[:, rows], export.v[:, rows])
            if is_fast:
                self._land(slots, [tuple(jax.device_put(
                    staged, self.host.device_sharding))],
                    [(0, j) for j in range(len(rows))])
            else:
                self.host.park([(slots, staged)])
            self.transfer_events += 2
            for i, slot in zip(rows, slots):
                page = Page(
                    page_id=self._next_id, request_id=request_id,
                    index_in_seq=export.index_in_seq[i], birth_step=step,
                    hbm_slot=slot if is_fast else None,
                    host_slot=None if is_fast else slot,
                    accesses=export.accesses[i],
                    tokens_used=export.tokens_used[i], last_used=step)
                self._next_id += 1
                self.pages[page.page_id] = page
                new_pages[i] = page
        seq = self._seq.setdefault(request_id, [])
        seq.extend(p for p in new_pages if p is not None)
        self.imported_pages += n
        return [p for p in new_pages if p is not None]

    def swap_out(self, page_id: int):
        """HBM -> host (single page; the batched path with M=1)."""
        self.swap_out_many([page_id])

    def swap_in(self, page_id: int):
        """host -> HBM (single page; the batched path with M=1)."""
        self.swap_in_many([page_id])

    # --------------------------------------------------------- queries
    def resident(self, page_id: int) -> bool:
        return self.pages[page_id].hbm_slot is not None

    def hbm_used(self) -> int:
        return sum(1 for p in self.pages.values() if p.hbm_slot is not None)

    def request_pages(self, request_id: int) -> List[Page]:
        """The request's ordered page list (shared prefix pages included) —
        read from the sequence table, NOT by scanning ``Page.request_id``:
        a shared page's allocator may be long finished."""
        return sorted(self._seq.get(request_id, ()),
                      key=lambda p: p.index_in_seq)
