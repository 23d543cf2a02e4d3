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
  counts the logical bytes leaving the fast tier.

* **Double-buffered prefetch** — while layer L's grouped GEMM dispatch is
  in flight, the predicted working set for the next layer is staged onto
  the device on a second buffer (``prefetch``), and the cache scatter is
  committed when that layer actually dispatches (``_commit_pending``).
  Prediction fuses recency (that layer's previous dispatch) with the
  guidance profile (hottest non-resident blocks by access count — the
  same counters the controller consumes).  A misprediction falls back to
  the blocking demand fetch, so results are bitwise-identical with the
  prefetcher on or off.

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

Modeled decode time: the engine cannot observe real PCIe overlap on a CPU
test host, so the store accumulates a deterministic modeled clock in the
``StepCostModel`` idiom (deliberately round constants): each dispatch adds
its weight-read time at fast-tier bandwidth to ``m_compute_s``; a blocking
demand fetch adds transfer time at slow-tier bandwidth plus a fixed launch
latency to ``m_blocked_s``; a committed prefetch adds only the part of
that cost the overlap window (the previous dispatch's compute plus two
dispatch launches) could not hide.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import MigrationPlan, MoveStats
from ..core.fragmentation import ChunkStats
from ..core.hwmodel import TPU_V5E, HardwareModel
from ..core.profiler import ArenaProfile, IntervalProfile
from .tier_io import HostTier, copy_rows

# Modeled-time constants (StepCostModel idiom: deliberately round numbers
# so overlap effects are deterministic on any host).
FETCH_LATENCY_S = 10e-6       # per batched host->HBM staged transfer
DISPATCH_OVERHEAD_S = 20e-6   # per jitted dispatch launch

_WEIGHTS = ("w_gate", "w_up", "w_down")


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
    hide_s: float                 # modeled overlap window at issue time


class ExpertStore:
    """Host-authoritative expert weights with a bounded HBM cache.

    ``moe_params`` is the engine's stacked MoE param dict —
    ``w_gate/w_up/w_down`` shaped ``(n_layers, E, ...)``.  The store takes
    bitwise copies into its own tier layout; the caller may drop its dense
    resident arrays afterwards.
    """

    def __init__(self, moe_params: Mapping[str, jax.Array], n_layers: int,
                 n_experts: int, cache_slots: int, *,
                 double_buffer: bool = True, hw: HardwareModel = TPU_V5E,
                 window_bytes: int = 0, device=None):
        if cache_slots <= 0:
            raise ValueError(
                f"ExpertStore needs at least one cache slot, got "
                f"{cache_slots} (ServeConfig.expert_cache_size)")
        L, E = n_layers, n_experts
        self.n_layers = L
        self.n_experts = E
        self.cache_slots = min(cache_slots, L * E)
        self.double_buffer = double_buffer
        self.hw = hw
        self.window_bytes = int(window_bytes)
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
        self._last_prefetched: Dict[int, FrozenSet[int]] = {}
        self.dispatching: Dict[int, FrozenSet[int]] = {}
        self.prev_needed: Dict[int, List[int]] = {}
        self._last_window_s = 0.0
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
        self.m_compute_s = 0.0
        self.m_blocked_s = 0.0

    def take_rental_bytes(self) -> int:
        """Drain bytes demand-fetched since the last drain — the engine
        feeds them to ``GuidanceRuntime.record_rental`` (slow-tier rent
        actually paid, the ski-rental input), mirroring KV swap-ins."""
        nb, self._rental_bytes = self._rental_bytes, 0
        return nb

    # ------------------------------------------------------------ movement
    def _transfer(self, pairs: Sequence[Tuple[int, int]]):
        """ONE batched host->device transfer per weight array of the layer
        slabs holding the blocks.  Returns ``HostTier.fetch``'s staging;
        the cache writes happen at `_install`."""
        staged = self.host.fetch([self.chunk_id(l, e) for l, e in pairs])
        self.transfer_events += len(_WEIGHTS)
        self.bytes_fetched += self.block_bytes * len(pairs)
        return staged

    def _install(self, pairs: Sequence[Tuple[int, int]], slots: Sequence[int],
                 staged, step: int, *, prefetched: bool) -> None:
        self.w_gate_cache, self.w_up_cache, self.w_down_cache = copy_rows(
            (self.w_gate_cache, self.w_up_cache, self.w_down_cache),
            slots, *staged, axis=0)
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

    def _evictable(self, protect: FrozenSet[int]) -> List[ExpertBlock]:
        out = [b for b in self.blocks.values()
               if b.slot is not None and b.slot not in protect
               and b.slot not in self._reserved]
        # LRU with a total deterministic order.
        out.sort(key=lambda b: (b.last_used, b.layer, b.expert))
        return out

    def _acquire_slots(self, n: int, protect: FrozenSet[int]) -> List[int]:
        """Take ``n`` cache slots: free list first, then LRU eviction of
        unprotected residents.  Returns fewer than ``n`` when the cache is
        too pinned — callers decide whether that is an error."""
        slots: List[int] = []
        while self._free and len(slots) < n:
            slots.append(self._free.pop())
        if len(slots) < n:
            for b in self._evictable(protect)[:n - len(slots)]:
                slots.append(self._evict(b))
        return slots

    # ------------------------------------------------------------ prefetch
    def _commit_pending(self, layer: int, step: int) -> None:
        pend = self._pending.pop(layer, None)
        if pend is None:
            return
        self._reserved.difference_update(pend.slots)
        pairs = [(layer, e) for e in pend.experts]
        self._install(pairs, pend.slots, pend.staged, step, prefetched=True)
        cost = (len(pairs) * self.block_bytes
                / (self.hw.slow.read_bw_GBps * 1e9) + FETCH_LATENCY_S)
        self.m_blocked_s += max(0.0, cost - pend.hide_s)
        self._last_prefetched[layer] = frozenset(pend.experts)

    def prefetch(self, layer: int, step: int,
                 predicted: Optional[Sequence[int]] = None) -> int:
        """Issue the double-buffer for ``layer``: stage its predicted
        working set onto the device while the current dispatch computes.

        ``predicted`` is the engine's speculative-gating forecast (the
        layer's own router applied to the residual stream one attention
        delta early, hottest first) — when given, exactly its non-resident
        members are staged.  Without it (the wrap-around to the next
        step's first dispatch, whose input token does not exist yet) the
        store falls back to recency + the guidance profile's hottest
        blocks.  Returns the number of blocks put in flight."""
        if not self.double_buffer or layer in self._pending:
            return 0
        if predicted is not None:
            want_set = [int(e) for e in predicted]
            targets = [e for e in want_set if not self.is_resident(layer, e)]
        else:
            prev = self.prev_needed.get(layer)
            if not prev:
                return 0                  # never dispatched: no prediction
            want = len(prev)
            # Recency first (last dispatch of this layer), then the layer's
            # hottest blocks by profile access count — guided prefetch.
            ranked = sorted(
                (self.blocks[(layer, e)] for e in range(self.n_experts)
                 if e not in prev),
                key=lambda b: (-b.accesses, b.expert))
            want_set = list(prev)
            targets = [e for e in prev if not self.is_resident(layer, e)]
            targets += [b.expert for b in ranked
                        if b.slot is None][:max(want - len(targets), 0)]
            targets = targets[:want]
        if not targets:
            return 0
        # A prefetch must not evict what it is predicting around: protect
        # the current dispatch's pins AND the predicted set's already-
        # resident members (evicting those would turn forecast hits into
        # the very demand misses the buffer exists to avoid).
        protect = self._pinned | frozenset(
            self.blocks[(layer, e)].slot for e in want_set
            if self.blocks[(layer, e)].slot is not None)
        slots = self._acquire_slots(len(targets), protect)
        if len(slots) < len(targets):
            self.dropped_prefetches += len(targets) - len(slots)
            targets = targets[:len(slots)]
        if not targets:
            return 0
        staged = self._transfer([(layer, e) for e in targets])
        self._reserved.update(slots)
        self._pending[layer] = _PendingFetch(
            layer, targets, slots, staged, self._last_window_s)
        return len(targets)

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
        counts = np.asarray(counts)
        assert counts.shape == (self.n_experts,), counts.shape
        needed = [int(e) for e in np.nonzero(counts)[0]]
        self._commit_pending(layer, step)

        committed = self._last_prefetched.pop(layer, frozenset())
        self.prefetch_hits += len(committed.intersection(needed))

        resident_slots = frozenset(
            self.blocks[(layer, e)].slot for e in needed
            if self.blocks[(layer, e)].slot is not None)
        missing = [e for e in needed
                   if self.blocks[(layer, e)].slot is None]
        if missing:
            slots = self._acquire_slots(len(missing), resident_slots)
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
            self.m_blocked_s += (
                len(missing) * self.block_bytes
                / (self.hw.slow.read_bw_GBps * 1e9) + FETCH_LATENCY_S)

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

        t_comp = ((self.window_bytes + len(needed) * self.block_bytes)
                  / (self.hw.fast.read_bw_GBps * 1e9))
        self.m_compute_s += t_comp + DISPATCH_OVERHEAD_S
        # Overlap a prefetch issued *now* can hide: this dispatch's weight
        # reads plus the two jitted launches before the next FFN needs it.
        self._last_window_s = t_comp + 2 * DISPATCH_OVERHEAD_S
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
