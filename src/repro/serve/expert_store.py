"""Tiered MoE expert-weight store: guided expert tiering (ROADMAP item 1).

The third tier-object class under the paper's online-guidance loop, after
KV pages (PR 4) and shared prefixes (PR 6).  MoE expert FFN blocks are the
largest tier-able objects in the system; keeping only a bounded cache of
them in HBM opens larger-than-HBM model configs on the same hardware.

Three layers live here:

* **ExpertStore** — owns per-(layer, expert) weight blocks.  The host tier
  is the authoritative, immutable copy of every block, parked in
  pinned_host memory one slab per layer (``serve/tier_io.HostTier``); the
  HBM tier is a bounded ``cache_slots``-row cache shared by all layers.
  A fetch is one batched ``jax.device_put`` per weight array of the
  layers' slabs holding the blocks, then in-place cache row writes.
  Expert weights never change, so *demotion is metadata-only* — the slot
  is released and the host copy stays authoritative; ``bytes_demoted``
  counts the logical bytes leaving the fast tier.  A full cache gives up
  cold blocks first (not named in their layer's most recent dispatch),
  least recently used first, then hot blocks by their layer's next
  dispatch in the fixed layer cycle, farthest first: Belady's rule on
  the one future the store knows, so a cyclic working set larger than
  the cache keeps whole layers resident instead of evicting each block
  just before its next use.

* **Prefetch** — while layer L's grouped GEMM dispatch is in flight, the
  predicted working set for the next layer is staged onto the device on a
  second buffer and takes its cache slots (``prefetch``); the cache
  scatter is committed when that layer actually dispatches
  (``_commit_pending``).  Prediction fuses recency (that layer's previous
  dispatch) with the guidance profile (hottest non-resident blocks by
  access count — the same counters the controller consumes).  Where the
  engine's forecast names every expert, ``lead`` also puts the whole
  slabs of the next layers that miss blocks on the link, skipping layers
  that are resident, up to ``LEAD`` slabs staged or in flight; a slab
  staged ahead takes no cache slot until its layer is next, so victims
  and slots are what the one-layer lead picks.  A misprediction falls
  back to the blocking demand fetch, so a prefetch never changes a
  result.

* **ExpertBackend** — the ``TierBackend`` face: arena = one layer's expert
  population, chunk = one expert block.  Per-dispatch ``group_sizes`` from
  ``route_tokens`` double as the access profile (no extra
  instrumentation); ski-rental decides promote/demote; blocks named in the
  most recent dispatch of their layer never demote (the
  never-demote-while-dispatching rule), because the slot map handed to an
  in-flight grouped GEMM must keep meaning what it said.

Correctness bar: the cache-slot indirection rides the grouped GEMM's
existing ``group_experts`` remap (``models/moe.apply_dropless_flat``), so
the result does not depend on which cache row holds a block: bitwise the
same at any cache size that fits each dispatch's working set (and, on the
CPU, bitwise the fully-resident path's; the TPU rounds that different
program differently).  A working set that cannot fit raises
``ExpertCacheMissError`` naming ``ServeConfig.expert_cache_size`` — never
a silent wrong-weight dispatch.

The store keeps counts, not times.  What its work costs is read from a
profiler trace of a chip run: ``dispatch`` and ``prefetch`` run under the
spans ``repro.expert.dispatch`` and ``repro.expert.prefetch``, and each
host-to-HBM transfer and its cache row writes under
``repro.expert.transfer`` (``core/spans.py``).  ``committed_blocks`` and
``landed_blocks`` count the prefetched blocks committed and those whose
slab had already landed in HBM by then: how much of the transfer the
lead hid.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import MigrationPlan, MoveStats
from ..core.fragmentation import ChunkStats
from ..core.profiler import ArenaProfile, IntervalProfile
from ..core.spans import span
from .tier_io import HostTier, copy_rows

_WEIGHTS = ("w_gate", "w_up", "w_down")
# Layer slabs on the device ahead of their commit at most: one on the link,
# one landed and waiting.  Two keep a saturated link busy.
LEAD = 2


class ExpertCacheMissError(RuntimeError):
    """A dispatch's expert working set cannot fit the HBM expert cache.

    Raised *before* any grouped GEMM runs with an incomplete slot map —
    the tiered path never silently dispatches against wrong weights.  The
    message names the knob (``ServeConfig.expert_cache_size``)."""


@dataclasses.dataclass
class ExpertBlock:
    """Tier state for one (layer, expert) FFN weight block."""

    layer: int
    expert: int
    slot: Optional[int] = None    # HBM cache row, None = host-only
    accesses: float = 0.0         # routed-token count (decayed by reweight)
    birth_step: int = 0
    last_used: int = -1           # step of the last dispatch that read it
    fetches: int = 0


@dataclasses.dataclass
class _PendingFetch:
    """One in-flight double-buffer: blocks staged on device, scatter
    deferred until the target layer dispatches."""

    layer: int
    experts: List[int]
    slots: List[int]
    staged: tuple                 # (staged slabs, locations): HostTier.fetch


class ExpertStore:
    """Host-authoritative expert weights with a bounded HBM cache.

    ``moe_params`` is the engine's stacked MoE param dict —
    ``w_gate/w_up/w_down`` shaped ``(n_layers, E, ...)``.  The store takes
    bitwise copies into its own tier layout; the caller may drop its dense
    resident arrays afterwards.
    """

    def __init__(self, moe_params: Mapping[str, jax.Array], n_layers: int,
                 n_experts: int, cache_slots: int, *, device=None):
        if cache_slots <= 0:
            raise ValueError(
                f"ExpertStore needs at least one cache slot, got "
                f"{cache_slots} (ServeConfig.expert_cache_size)")
        L, E = n_layers, n_experts
        self.n_layers = L
        self.n_experts = E
        self.cache_slots = min(cache_slots, L * E)
        self.device = device if device is not None else jax.devices()[0]
        weights = [moe_params[name] for name in _WEIGHTS]
        for name, w in zip(_WEIGHTS, weights):
            if w.shape[:2] != (L, E):
                raise ValueError(
                    f"expert weight {name} is shaped {w.shape}; expected "
                    f"(n_layers={L}, n_experts={E}, ...)")
        self.block_bytes = sum(int(np.prod(w.shape[2:])) * w.dtype.itemsize
                               for w in weights)
        # Host tier, addressed by chunk id: one slab per layer holding its
        # E blocks of every weight.  The copy runs one layer at a time and
        # waits for it, so HBM never holds more than one extra layer.
        self.host = HostTier(self.device, axis=0)
        for l in range(L):
            jax.block_until_ready(self.host.park(
                [([self.chunk_id(l, e) for e in range(E)],
                  tuple(w[l] for w in weights))]))
        self.w_gate_cache, self.w_up_cache, self.w_down_cache = (
            jnp.zeros((self.cache_slots,) + w.shape[2:], w.dtype,
                      device=self.host.device_sharding) for w in weights)

        self.blocks: Dict[Tuple[int, int], ExpertBlock] = {
            (l, e): ExpertBlock(l, e) for l in range(L) for e in range(E)}
        self._free: List[int] = list(range(self.cache_slots))
        self._owner: List[Optional[Tuple[int, int]]] = (
            [None] * self.cache_slots)
        self._pinned: FrozenSet[int] = frozenset()   # last dispatch's slots
        self._reserved: set = set()                  # pending-prefetch slots
        self._pending: Dict[int, _PendingFetch] = {}
        # Whole layer slabs staged ahead (``lead``), no slots taken yet:
        # layer -> (HostTier.fetch's staged slabs, each expert's location).
        self._ahead: Dict[int, Tuple[list, Dict[int, Tuple[int, int]]]] = {}
        self._next = 0                  # the layer that dispatches next
        self._last_prefetched: Dict[int, FrozenSet[int]] = {}
        self.dispatching: Dict[int, FrozenSet[int]] = {}
        self.prev_needed: Dict[int, List[int]] = {}
        self._rental_bytes = 0
        self.reset_counters()

    # ------------------------------------------------------------ identity
    def chunk_id(self, layer: int, expert: int) -> int:
        return layer * self.n_experts + expert

    def from_chunk(self, cid: int) -> Tuple[int, int]:
        return divmod(cid, self.n_experts)

    def is_resident(self, layer: int, expert: int) -> bool:
        return self.blocks[(layer, expert)].slot is not None

    def resident_count(self) -> int:
        return self.cache_slots - len(self._free) - len(self._reserved)

    @property
    def cache_bytes(self) -> int:
        return self.cache_slots * self.block_bytes

    # ------------------------------------------------------------ counters
    def reset_counters(self) -> None:
        self.demand_fetches = 0
        self.prefetch_fetches = 0
        self.prefetch_hits = 0
        self.dropped_prefetches = 0
        self.evictions = 0
        self.bytes_fetched = 0
        self.transfer_events = 0
        self.needed_blocks = 0        # blocks dispatches read
        self.resident_hits = 0        # ... already resident beforehand
        self.committed_blocks = 0     # prefetched blocks committed
        self.landed_blocks = 0        # ... whose slab had landed by then

    def take_rental_bytes(self) -> int:
        """Drain bytes demand-fetched since the last drain — the engine
        feeds them to ``GuidanceRuntime.record_rental`` (slow-tier rent
        actually paid, the ski-rental input), mirroring KV swap-ins."""
        nb, self._rental_bytes = self._rental_bytes, 0
        return nb

    # ------------------------------------------------------------ movement
    def _send(self, pairs: Sequence[Tuple[int, int]]):
        """ONE batched host->device transfer per weight array of the layer
        slabs holding the blocks: ``HostTier.fetch``'s staging."""
        with span("repro.expert.transfer", blocks=len(pairs)):
            staged = self.host.fetch([self.chunk_id(l, e) for l, e in pairs])
        self.transfer_events += len(_WEIGHTS)
        return staged

    def _transfer(self, pairs: Sequence[Tuple[int, int]]):
        """The staging of ``pairs`` for `_install`: from their layer's slab
        staged ahead (``lead``) when there is one, else sent now."""
        layers = {l for l, _ in pairs}
        ahead = (self._ahead.pop(layers.pop(), None) if len(layers) == 1
                 else None)
        if ahead is None:
            staged = self._send(pairs)
        else:
            slabs, where = ahead
            staged = (slabs, [where[e] for _, e in pairs])
        self.bytes_fetched += self.block_bytes * len(pairs)
        return staged

    def _install(self, pairs: Sequence[Tuple[int, int]], slots: Sequence[int],
                 staged, step: int, *, prefetched: bool) -> None:
        with span("repro.expert.transfer", blocks=len(pairs)):
            self.w_gate_cache, self.w_up_cache, self.w_down_cache = (
                copy_rows((self.w_gate_cache, self.w_up_cache,
                           self.w_down_cache), slots, *staged, axis=0))
        for (l, e), s in zip(pairs, slots):
            b = self.blocks[(l, e)]
            b.slot = int(s)
            b.last_used = step
            b.fetches += 1
            self._owner[int(s)] = (l, e)
        if prefetched:
            self.prefetch_fetches += len(pairs)
        else:
            self.demand_fetches += len(pairs)
            self._rental_bytes += self.block_bytes * len(pairs)

    def _evict(self, block: ExpertBlock) -> int:
        """Metadata-only demotion: the host copy is authoritative and
        immutable, so no bytes move back."""
        s = block.slot
        assert s is not None
        block.slot = None
        self._owner[s] = None
        self.evictions += 1
        return s

    def _evictable(self, protect: FrozenSet[int],
                   layer: int) -> List[ExpertBlock]:
        """Unprotected residents in victim order, for a fetch serving
        ``layer``.  Layers dispatch in a fixed cycle, so a block named in
        its layer's most recent dispatch (hot) is next used at that
        layer's next dispatch: Belady's rule evicts the farthest first.
        Blocks that dispatch did not name (cold) have no known next use
        and go before any hot block, least recently used first."""
        out = [b for b in self.blocks.values()
               if b.slot is not None and b.slot not in protect
               and b.slot not in self._reserved]
        L = self.n_layers

        def order(b: ExpertBlock):
            if b.expert not in self.dispatching.get(b.layer, ()):
                return (0, b.last_used, b.layer, b.expert)
            return (1, -((b.layer - layer) % L), b.layer, b.expert)

        out.sort(key=order)
        return out

    def _acquire_slots(self, n: int, protect: FrozenSet[int],
                       layer: int) -> List[int]:
        """Take ``n`` cache slots for ``layer``: free list first, then
        eviction of unprotected residents in ``_evictable``'s order.
        Returns fewer than ``n`` when the cache is too pinned — callers
        decide whether that is an error."""
        slots: List[int] = []
        while self._free and len(slots) < n:
            slots.append(self._free.pop())
        if len(slots) < n:
            for b in self._evictable(protect, layer)[:n - len(slots)]:
                slots.append(self._evict(b))
        return slots

    # ------------------------------------------------------------ prefetch
    def _commit_pending(self, layer: int, step: int) -> None:
        pend = self._pending.pop(layer, None)
        if pend is None:
            return
        self._reserved.difference_update(pend.slots)
        slabs, locs = pend.staged
        landed = [all(a.is_ready() for a in slab) for slab in slabs]
        self.committed_blocks += len(locs)
        self.landed_blocks += sum(landed[p] for p, _ in locs)
        pairs = [(layer, e) for e in pend.experts]
        self._install(pairs, pend.slots, pend.staged, step, prefetched=True)
        self._last_prefetched[layer] = frozenset(pend.experts)

    def _ranking(self, layer: int) -> List[int]:
        """Every expert of ``layer``, likeliest first: those its last
        dispatch named (recency), then the rest by the guidance profile's
        access count."""
        prev = self.prev_needed.get(layer, [])
        rest = sorted((self.blocks[(layer, e)] for e in range(self.n_experts)
                       if e not in prev), key=lambda b: (-b.accesses, b.expert))
        return list(prev) + [b.expert for b in rest]

    def prefetch(self, layer: int, step: int,
                 predicted: Optional[Sequence[int]] = None) -> int:
        """Issue the double-buffer for ``layer``: stage its predicted
        working set onto the device while the current dispatch computes.

        ``predicted`` is the engine's forecast, likeliest first: the
        layer's own router applied to the residual stream one attention
        delta early, or every expert (``lead``) — when given, exactly its
        non-resident members are staged, out of the layer's slab staged
        ahead when there is one.  Without it (the wrap-around to the next
        step's first dispatch, whose input token does not exist yet) the
        store falls back to recency + the guidance profile's hottest
        blocks.  Returns the number of blocks put in flight."""
        with span("repro.expert.prefetch", layer=layer):
            if layer in self._pending:
                return 0
            if predicted is not None:
                want_set = [int(e) for e in predicted]
                targets = [e for e in want_set
                           if not self.is_resident(layer, e)]
            else:
                want_set = self.prev_needed.get(layer)
                if not want_set:
                    return 0                  # never dispatched: no prediction
                # Recency first (last dispatch of this layer), then the
                # layer's hottest blocks by profile access count — guided
                # prefetch.
                targets = [e for e in self._ranking(layer)
                           if not self.is_resident(layer, e)][:len(want_set)]
            if not targets:
                return 0
            # A prefetch must not evict what it is predicting around: protect
            # the current dispatch's pins AND the predicted set's already-
            # resident members (evicting those would turn forecast hits into
            # the very demand misses the buffer exists to avoid).
            protect = self._pinned | frozenset(
                self.blocks[(layer, e)].slot for e in want_set
                if self.blocks[(layer, e)].slot is not None)
            slots = self._acquire_slots(len(targets), protect, layer)
            if len(slots) < len(targets):
                self.dropped_prefetches += len(targets) - len(slots)
                targets = targets[:len(slots)]
            if not targets:
                return 0
            staged = self._transfer([(layer, e) for e in targets])
            self._reserved.update(slots)
            self._pending[layer] = _PendingFetch(layer, targets, slots, staged)
            return len(targets)

    def lead(self, step: int) -> None:
        """The prefetch when the forecast names every expert.  The layer
        that dispatches next is staged whole (``prefetch`` in
        ``_ranking``'s order) and takes its cache slots now, as under a
        one-layer lead.  Then the slabs of the first ``LEAD`` layers in
        cycle order that miss blocks are staged or put on the link; layers
        whose every block is resident are skipped, so the link keeps
        moving while they dispatch, and a slab staged ahead for a layer
        that is no longer among them is dropped.  A slab staged ahead
        takes no cache slot: its layer picks its victims and reserves its
        slots when it is next (or at its dispatch), so no layer that
        dispatches before it loses a block or a slot to it."""
        L, E = self.n_layers, self.n_experts
        nxt = self._next
        self.prefetch(nxt, step, predicted=self._ranking(nxt))
        lead: List[int] = []
        for d in range(L):
            m = (nxt + d) % L
            if any(self.blocks[(m, e)].slot is None for e in range(E)):
                lead.append(m)
                if len(lead) == LEAD:
                    break
        for m in [m for m in self._ahead if m not in lead]:
            del self._ahead[m]      # resident by now, or nearer layers miss
        for m in lead:
            if m not in self._pending and m not in self._ahead:
                with span("repro.expert.prefetch", layer=m):
                    slabs, locs = self._send([(m, e) for e in range(E)])
                self._ahead[m] = (slabs, dict(enumerate(locs)))

    # ------------------------------------------------------------ dispatch
    def dispatch(self, layer: int, counts, step: int) -> np.ndarray:
        """Make ``layer``'s routed experts resident and return the (E,)
        slot map for the grouped GEMM (−1 for absent-and-unrouted blocks).

        ``counts`` is the dispatch's per-expert routed-token histogram —
        the ``group_sizes`` the GEMM consumes anyway, doubling as the
        access profile.  Order of operations: commit any in-flight
        prefetch for this layer, pin the needed set (a block this dispatch
        reads must never lose its slot mid-dispatch), demand-fetch the
        misses in one batched transfer, then account.
        """
        with span("repro.expert.dispatch", layer=layer):
            counts = np.asarray(counts)
            assert counts.shape == (self.n_experts,), counts.shape
            needed = [int(e) for e in np.nonzero(counts)[0]]
            self.needed_blocks += len(needed)
            self.resident_hits += sum(
                1 for e in needed if self.blocks[(layer, e)].slot is not None)
            self._commit_pending(layer, step)

            committed = self._last_prefetched.pop(layer, frozenset())
            self.prefetch_hits += len(committed.intersection(needed))

            resident_slots = frozenset(
                self.blocks[(layer, e)].slot for e in needed
                if self.blocks[(layer, e)].slot is not None)
            missing = [e for e in needed
                       if self.blocks[(layer, e)].slot is None]
            if missing:
                slots = self._acquire_slots(len(missing), resident_slots,
                                            layer)
                if len(slots) < len(missing):
                    for s in slots:           # undo: nothing was transferred
                        self._free.append(s)
                    have = len(needed) - len(missing) + len(slots)
                    raise ExpertCacheMissError(
                        f"expert cache cannot hold layer {layer}'s dispatch "
                        f"working set: {len(needed)} distinct experts routed "
                        f"but only {have} fit a {self.cache_slots}-slot cache "
                        f"({len(self._reserved)} reserved by in-flight "
                        f"prefetch); raise ServeConfig.expert_cache_size or "
                        f"disable ServeConfig.expert_offchip")
                staged = self._transfer([(layer, e) for e in missing])
                self._install([(layer, e) for e in missing], slots, staged,
                              step, prefetched=False)

            slot_map = np.full(self.n_experts, -1, dtype=np.int32)
            for e in needed:
                b = self.blocks[(layer, e)]
                b.accesses += float(counts[e])
                b.last_used = step
                slot_map[e] = b.slot
            self._pinned = frozenset(
                int(slot_map[e]) for e in needed)
            self.dispatching[layer] = frozenset(needed)
            self.prev_needed[layer] = needed
            # A slab staged ahead that this dispatch did not take is stale.
            self._ahead.pop(layer, None)
            self._next = (layer + 1) % self.n_layers
            return slot_map

    # ----------------------------------------------------- controller face
    def drop_many(self, pairs: Sequence[Tuple[int, int]]
                  ) -> List[Tuple[int, int]]:
        """Demote blocks (metadata-only).  Blocks named in their layer's
        most recent dispatch, pinned or reserved slots are refused — the
        never-demote-while-dispatching rule."""
        dropped = []
        for l, e in pairs:
            b = self.blocks[(l, e)]
            if b.slot is None or b.slot in self._pinned \
                    or b.slot in self._reserved \
                    or e in self.dispatching.get(l, frozenset()):
                continue
            self._free.append(self._evict(b))
            dropped.append((l, e))
        return dropped

    def fetch_many(self, pairs: Sequence[Tuple[int, int]], step: int
                   ) -> Tuple[List[Tuple[int, int]], List[Tuple[int, int]]]:
        """Promote blocks into *free* slots only (one batched transfer);
        the controller never evicts on promote — refusals are reported
        back so the plan reflects reality."""
        with span("repro.expert.prefetch"):
            want = [(l, e) for l, e in pairs if not self.is_resident(l, e)]
            room = len(self._free)
            take, refused = want[:room], want[room:]
            if take:
                slots = [self._free.pop() for _ in take]
                staged = self._transfer(take)
                self._install(take, slots, staged, step, prefetched=True)
            return take, refused

    def reweight(self, decay: float) -> None:
        for b in self.blocks.values():
            b.accesses *= decay

    def fast_resident_bytes(self) -> int:
        return sum(self.block_bytes for b in self.blocks.values()
                   if b.slot is not None)


class ExpertBackend:
    """``TierBackend`` over an ``ExpertStore``: arena = one layer's expert
    population, chunk = one (layer, expert) block.  Same contract as
    ``PagedKVBackend`` — demotions first, promotions bounded by free
    slots, refusals reflected into ``last_recs``."""

    name = "expert"

    def __init__(self, store: ExpertStore, clock):
        self.store = store
        self.clock = clock
        self.last_recs: Dict[int, bool] = {}
        self._telemetry: Dict[int, List[ChunkStats]] = {}

    # ------------------------------------------------------------- protocol
    def snapshot(self) -> IntervalProfile:
        st = self.store
        step = self.clock()
        rows: List[ArenaProfile] = []
        telemetry: Dict[int, List[ChunkStats]] = {}
        for l in range(st.n_layers):
            blocks = [st.blocks[(l, e)] for e in range(st.n_experts)]
            fast = sum(1 for b in blocks if b.slot is not None)
            rows.append(ArenaProfile(
                arena_id=l, site_id=l, label=f"moe_l{l}",
                accesses=sum(b.accesses for b in blocks),
                resident_bytes=len(blocks) * st.block_bytes,
                fast_fraction=fast / len(blocks)))
            telemetry[l] = [
                ChunkStats(chunk_id=st.chunk_id(l, b.expert),
                           nbytes=st.block_bytes, accesses=b.accesses,
                           age=step - b.birth_step,
                           fast=b.slot is not None)
                for b in blocks]
        self._telemetry = telemetry
        return IntervalProfile(step, rows, 0, 0.0)

    def telemetry(self) -> Mapping[int, Sequence[ChunkStats]]:
        return self._telemetry

    def reweight(self, decay: float) -> None:
        self.store.reweight(decay)

    def on_plan(self, plan: MigrationPlan) -> None:
        self.last_recs = dict(plan.chunk_placement)

    def enforce(self, plan: MigrationPlan) -> MoveStats:
        stats = MoveStats()
        st = self.store
        placement = sorted(plan.chunk_placement.items())
        demote = [st.from_chunk(cid) for cid, fast in placement if not fast]
        dropped = st.drop_many(demote)
        # Logical bytes leaving the fast tier; demotion is metadata-only
        # (immutable weights never copy back).
        stats.bytes_demoted = st.block_bytes * len(dropped)
        want = [st.from_chunk(cid) for cid, fast in placement if fast]
        done, refused = st.fetch_many(want, self.clock())
        stats.bytes_promoted = st.block_bytes * len(done)
        for l, e in refused:
            stats.dropped_promotions += 1
            self.last_recs[st.chunk_id(l, e)] = False
        return stats

    def fast_bytes(self) -> int:
        return self.store.fast_resident_bytes()
