"""Continuous-batching serving engine with guided KV-page tiering.

The engine serves dense/MoE decoder models from a paged two-tier KV cache
(serve/kvcache.py).  Each *request* is an allocation site; its pages are the
chunks.  The request lifecycle is explicit:

    waiting --admit--> [prefilling -->] active <--pause/resume--> paused
        ^                                 |                         |
        +------------- preempt ----------/ <----------------------/
    (any live state) ------------------- finish/cancel ---------> finished

* **Admission** order, preemption victims, decode packing, and the
  per-step prefill/decode budget split are POLICY decisions, delegated to
  a ``SchedulerPolicy`` (serve/scheduler.py; ``ServeConfig.scheduler``
  picks ``fifo`` — bitwise the pre-policy engine — ``priority``, or
  ``drr`` per-tenant fairness).  A request is admitted when its prompt's
  pages fit the pool's free logical capacity (no raw ``IndexError`` /
  ``MemoryError`` escapes for work that merely has to wait); admission
  never skips past a request that does not fit.  Requests that can
  *never* run — prompt + generation budget past
  ``max_pages_per_seq * page_size``, or a prompt bigger than the usable HBM
  pool — are rejected at ``add_request`` with an error naming the knob.
* **Prefill** is one-shot: a single jitted dispatch writes the whole
  prompt's K/V directly into page-table slots and attends with per-token
  causal lengths (``kernels.ops.paged_prefill``).  The chunked path
  (``prefill="chunked"``: step the prompt through decode one token at a
  time) survives as the bitwise-equality oracle.  With
  ``prefill_chunk_tokens > 0`` long prompts are instead INTERLEAVED: an
  admitted request enters a ``prefilling`` state and each engine step
  ingests at most that many prompt tokens (one bucketed dispatch per
  chunk, at the chunk's absolute start position) alongside the decode
  batch, so a 32k-token prompt cannot monopolize the step loop — and
  because one-shot == chunked == decode bitwise, interleaving changes
  WHEN tokens appear, never WHICH tokens.  With
  ``enable_prefix_cache`` the cross-request radix prefix cache
  (serve/prefix_cache.py) is consulted FIRST: matched full-page blocks are
  attached by reference (refcounted, copy-on-write) and the dispatch runs
  only over the uncovered suffix at its absolute positions — a full hit
  skips prefill entirely — with cached-vs-uncached logits bitwise-equal
  (K/V depend only on tokens and positions, and suffix == whole-prompt
  prefill by the one-shot == chunked == decode equality).
* **Scheduling** each step packs up to ``max_batch`` active requests in
  the policy's decode order under two budgets — usable HBM slots and free
  logical pages — so a batch can always be made resident without evicting
  its own members; requests that do not fit are starved this step, not
  crashed.
* **Preemption**: paused requests can lose their pages entirely (preempt by
  recompute — deterministic re-prefill of prompt+generated on resume makes
  this lossless, *because* one-shot prefill == decode bitwise) when the
  wait-queue head needs logical pages.
* **Decoding** samples INSIDE the jitted dispatch (``ops.sample_tokens``):
  each scheduled row's ``SamplingParams`` ride along as batched
  temperature/top-k/top-p/seed arrays, and the per-row PRNG key folds the
  token's absolute stream position — so preemption-by-recompute and
  one-shot-vs-chunked prefill replay to identical sampled streams, and
  ``temperature=0`` rows are bitwise-equal to greedy argmax.
* **Finish** carries a reason — ``stop`` (a sampled token hit
  ``SamplingParams.stop_token_ids``), ``length`` (``max_new`` /
  ``max_tokens`` exhausted), ``truncated`` (capacity) or ``cancelled``
  (``Engine.cancel`` withdrew a live request) — frees pages,
  prunes the request from ``engine.requests`` and its pages from the
  eviction policy's ``last_recs`` view; results move to
  ``engine.finished`` (drain with ``pop_finished``; per-reason totals in
  ``stats()``).

Most callers should not drive ``Engine`` directly: ``serve.api.LLM``
(``generate`` / ``submit`` streaming handles) is the front door behind
which all of this stays invisible.

Algorithm 1 itself is NOT implemented here: the engine exposes its page pool
to the shared controller through ``PagedKVBackend`` (a
``core.runtime.TierBackend``) and a ``GuidanceRuntime`` drives the paper's
machinery — profile -> age-fragmented thermos -> ski-rental -> page
migrations — at the decision interval.  All page movement (enforcement,
demand residency, eviction) goes through the pool's batched
``swap_in_many``/``swap_out_many``, so an N-page migration costs a constant
number of host<->device transfers per direction.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Deque, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import TPU_V5E, GuidanceConfig, GuidanceRuntime, HardwareModel, MoveStats
from ..core.fragmentation import ChunkStats
from ..core.profiler import ArenaProfile, IntervalProfile
from ..core.runtime import MigrationPlan
from ..dist.sharding import active_mesh
from ..models.layers import lm_head, mlp, rmsnorm, rope
from ..models.moe import apply_dropless_flat, moe_decode, route_tokens
from ..models.transformer import Model
from .eviction import make_eviction_policy
from .expert_store import ExpertBackend, ExpertCacheMissError, ExpertStore
from .kvcache import PageExport, PagedKVPool
from .prefix_cache import PrefixBackend, PrefixCache
from .sampling import DEFAULT_MAX_TOKENS, SamplingParams
from .scheduler import make_scheduler_policy

F32 = jnp.float32

# Every engine program rounds each bfloat16 value where the program says,
# whatever XLA fuses around it: with excess precision allowed (XLA's
# default) a fusion may carry a value in float32 past its bfloat16 cast, so
# results would depend on where the jit boundaries fall.  This is not
# enough to make the tiered layer-by-layer pipeline agree bitwise with the
# resident scan on the TPU (measured: their streams still part); each path
# agrees with itself across placements, and both with the f32 reference.
_jit = functools.partial(
    jax.jit, compiler_options={"xla_allow_excess_precision": False})


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 4
    page_size: int = 16
    hbm_pages: int = 64
    host_pages: int = 256
    policy: str = "gdt"            # gdt | lru | fifo (eviction registry)
    interval_steps: int = 16
    strategy: str = "thermos"
    num_fragments: int = 4
    max_pages_per_seq: int = 32
    # Algorithm 1's optional ReweightProfile: decay access counters each
    # interval so placement tracks recent behaviour (sessions pause/resume
    # far faster than HPC phase shifts, so serving defaults to decaying).
    access_decay: float = 0.5
    # Prompt ingestion: "one_shot" = single jitted dispatch per prompt;
    # "chunked" = step prompt tokens through decode (the bitwise oracle).
    prefill: str = "one_shot"
    # Scheduling policy (serve/scheduler.py registry): "fifo" is bitwise
    # the pre-policy engine; "priority" = strict classes + EDF; "drr" =
    # deficit-round-robin per-tenant fairness.
    scheduler: str = "fifo"
    # Chunked-prefill interleaving budget: > 0 caps how many prompt tokens
    # may ingest per engine step (one_shot mode only — an admitted request
    # sits in ``prefilling`` state and co-schedules with decode).  0 keeps
    # eager whole-suffix prefill at admission (bitwise the pre-policy
    # engine).
    prefill_chunk_tokens: int = 0
    # Cross-request radix prefix cache (serve/prefix_cache.py): requests
    # whose prompts start with the same full-page token blocks share those
    # pages by reference and prefill only the uncovered suffix.  Off by
    # default: sharing changes page-lifetime accounting (cached pages
    # outlive their requests), so workloads opt in.
    enable_prefix_cache: bool = False
    # A prefix enters the cache only when it spans at least this many FULL
    # pages — gates tree churn from trivially short shared prefixes.
    min_prefix_pages: int = 1
    # Debug: copy every scheduled row's logits to host into
    # ``engine.last_logits`` (a full (B, vocab) transfer per step — keep
    # off on the decode hot path; the parity tests turn it on).
    keep_logits: bool = False
    # -------- guided expert-weight tiering (serve/expert_store.py) -------
    # Keep MoE expert FFN weights host-resident and dispatch through a
    # bounded HBM expert cache.  Decoding runs layer-by-layer (router picks
    # sync to the host between attention and FFN); its result does not
    # depend on the cache size, and on the CPU it is bitwise-equal to the
    # resident single-scan path (on the TPU the two programs round
    # differently).
    expert_offchip: bool = False
    # HBM cache capacity in expert blocks, shared across layers; 0 means
    # every (layer, expert) block fits (n_layers * n_experts slots).  Must
    # hold at least one dispatch's working set:
    # min(n_experts, max_batch * top_k).
    expert_cache_size: int = 0
    # Double-buffered prefetch: while layer L's grouped GEMM is in flight,
    # the predicted working set for the next layer stages on a second
    # buffer.  Off = every miss is a blocking demand fetch (same results,
    # more modeled stall).
    expert_double_buffer: bool = True


@dataclasses.dataclass
class Request:
    request_id: int
    tokens: List[int]
    max_new: int
    params: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    generated: List[int] = dataclasses.field(default_factory=list)
    # waiting | prefilling | active | paused | preempted | finished
    state: str = "waiting"
    pos: int = 0                   # tokens written to KV so far
    last_scheduled: int = 0
    # Step this request (re-)entered the wait queue — admission-wait
    # accounting and the deadline base for SLO-aware policies.
    queued_step: int = 0
    truncated: bool = False        # finished early for capacity, not EOS
    # stop | length | truncated | cancelled
    finish_reason: Optional[str] = None

    @property
    def context(self) -> List[int]:
        """Prompt + everything generated so far — what a (re-)prefill must
        ingest (minus the final token, which the next decode step feeds)."""
        return self.tokens + self.generated


@dataclasses.dataclass
class RequestTicket:
    """The serializable identity of an in-flight request — everything a
    DIFFERENT engine needs to continue its stream bitwise.

    ``(prompt, params, generated)`` pins the token stream completely: the
    sampling seed is either explicit in ``params`` or derived from
    ``request_id`` (engine._run_batch), and the PRNG folds the absolute
    stream position, so replaying prompt+generated through prefill on any
    replica resumes the identical sampled stream (the
    preemption-by-recompute guarantee, applied across engines).  The page
    table is deliberately NOT here — it is reconstructible (cold path) or
    rides along separately as a ``PageExport`` (warm path)."""

    request_id: int
    prompt: List[int]
    max_new: int
    params: SamplingParams
    generated: List[int] = dataclasses.field(default_factory=list)
    # Set once the owning engine finished the request — a crashed replica's
    # undrained result is rebuilt from the ticket, never re-decoded.
    finish_reason: Optional[str] = None


class PagedKVBackend:
    """``TierBackend`` over the engine's paged KV pool.

    Arena = one request's page list; chunk = one page.  ``enforce`` is
    capacity-safe: the reserved scratch slot never appears in the free list,
    demotions run first, and promotions that would exceed the free HBM slots
    are *refused* — and reflected back into ``last_recs`` so the eviction
    policy sees the placement that actually exists, not the one that was
    merely planned.  Each direction is realized as ONE batched pool
    migration, not a per-page loop.
    """

    name = "paged_kv"

    def __init__(self, pool: PagedKVPool, requests: Dict[int, Request],
                 clock):
        self.pool = pool
        self.requests = requests
        self.clock = clock
        self.last_recs: Dict[int, bool] = {}   # page_id -> recommended fast
        self._telemetry: Dict[int, List[ChunkStats]] = {}

    # ------------------------------------------------------------- protocol
    def snapshot(self) -> IntervalProfile:
        rows: List[ArenaProfile] = []
        telemetry: Dict[int, List[ChunkStats]] = {}
        page_bytes = self.pool.page_bytes
        step = self.clock()
        for rid in self.requests:
            # Shared prefix pages are the PrefixBackend's tier objects —
            # profiling them per-request would double-govern one page under
            # two controllers (and double-count its accesses).
            pages = [p for p in self.pool.request_pages(rid)
                     if not p.shared]
            if not pages:
                continue
            fast_pages = sum(1 for p in pages if p.hbm_slot is not None)
            rows.append(ArenaProfile(
                arena_id=rid, site_id=rid, label=f"req{rid}",
                accesses=sum(p.accesses for p in pages),
                resident_bytes=len(pages) * page_bytes,
                fast_fraction=fast_pages / len(pages)))
            telemetry[rid] = [
                ChunkStats(chunk_id=p.page_id, nbytes=page_bytes,
                           accesses=p.accesses,
                           age=step - p.birth_step,
                           fast=p.hbm_slot is not None)
                for p in pages]
        self._telemetry = telemetry
        return IntervalProfile(step, rows, 0, 0.0)

    def telemetry(self) -> Mapping[int, Sequence[ChunkStats]]:
        return self._telemetry

    def reweight(self, decay: float) -> None:
        # Float counters: int(1 * 0.5) would zero any page with a single
        # access per interval, erasing the recency ordering decay exists to
        # preserve.  Shared pages decay under the PrefixBackend instead.
        for p in self.pool.pages.values():
            if not p.shared:
                p.accesses = p.accesses * decay

    def on_plan(self, plan: MigrationPlan) -> None:
        # Track the plan every interval (even when the break-even rule says
        # "wait") — the guided eviction policy keys off it.
        self.last_recs = dict(plan.chunk_placement)

    def enforce(self, plan: MigrationPlan) -> MoveStats:
        stats = MoveStats()
        pages = self.pool.pages
        page_bytes = self.pool.page_bytes
        # Demotions first (one batched transfer): free slots for promotions.
        demote = [pid for pid, fast in plan.chunk_placement.items()
                  if not fast and pid in pages
                  and pages[pid].hbm_slot is not None]
        self.pool.swap_out_many(demote)
        stats.bytes_demoted = page_bytes * len(demote)
        # Promotions (one batched transfer), bounded by free HBM slots.
        want = [pid for pid, fast in plan.chunk_placement.items()
                if fast and pid in pages and pages[pid].hbm_slot is None]
        room = len(self.pool.free_hbm)
        promote, refused = want[:room], want[room:]
        self.pool.swap_in_many(promote)
        stats.bytes_promoted = page_bytes * len(promote)
        for pid in refused:
            stats.dropped_promotions += 1
            self.last_recs[pid] = False
        return stats

    def forget_pages(self, page_ids: Sequence[int]) -> None:
        """Drop freed pages from the recommendation view so ``last_recs``
        never accumulates stale ids across request generations."""
        for pid in page_ids:
            self.last_recs.pop(pid, None)

    def fast_bytes(self) -> int:
        return self.pool.hbm_used() * self.pool.page_bytes


class Engine:
    """One serving engine on one device (``device``, default the first):
    its params, KV pools and expert store all live there, so replicas
    behind a ``Router`` can each own a chip."""

    def __init__(self, model: Model, params, cfg: ServeConfig,
                 hw: HardwareModel = TPU_V5E, device=None):
        assert model.cfg.family in ("dense", "moe"), \
            "paged engine serves decoder LMs"
        if model.cfg.family == "moe" and model.cfg.moe_parallelism == "ep":
            # Fail at construction, not mid-decode: an ep pad target that
            # doesn't divide over the live mesh's model axis would otherwise
            # surface as a shape error deep inside the jitted step.
            mesh = active_mesh()
            if mesh is not None and "model" in mesh.shape:
                model.moe_cfg.validate_ep_axis(int(mesh.shape["model"]))
        if cfg.prefill not in ("one_shot", "chunked"):
            raise ValueError(
                f"ServeConfig.prefill must be 'one_shot' or 'chunked', "
                f"got {cfg.prefill!r}")
        if cfg.prefill_chunk_tokens < 0:
            raise ValueError(
                f"ServeConfig.prefill_chunk_tokens must be >= 0, got "
                f"{cfg.prefill_chunk_tokens}")
        if cfg.expert_offchip:
            if model.cfg.family != "moe":
                raise ValueError(
                    "ServeConfig.expert_offchip requires a MoE model: "
                    f"family={model.cfg.family!r} has no expert weights "
                    "to tier")
            if model.cfg.moe_parallelism == "ep":
                raise ValueError(
                    "ServeConfig.expert_offchip drives the flat dropless "
                    "dispatch; ep parallelism already shards experts "
                    "across the mesh — pick one placement mechanism")
            E = model.moe_cfg.padded_experts
            floor = min(E, cfg.max_batch * model.moe_cfg.top_k)
            size = cfg.expert_cache_size or model.cfg.n_layers * E
            if cfg.expert_cache_size < 0 or 0 < size < floor:
                raise ValueError(
                    f"ServeConfig.expert_cache_size={cfg.expert_cache_size}"
                    f" cannot hold one dispatch's expert working set: a "
                    f"decode batch of max_batch={cfg.max_batch} rows with "
                    f"top_k={model.moe_cfg.top_k} picks can route up to "
                    f"min(n_experts={E}, max_batch*top_k)={floor} distinct "
                    f"experts in one layer; raise expert_cache_size to at "
                    f"least {floor} (0 = fully resident cache) or lower "
                    f"max_batch")
        self.model = model
        self.cfg = cfg
        self.hw = hw
        self.device = device if device is not None else jax.devices()[0]
        mc = model.cfg
        self.pool = PagedKVPool(
            n_layers=mc.n_layers, page_size=cfg.page_size,
            kv_heads=mc.kv_heads, head_dim=model.attn_cfg.head_dim,
            hbm_pages=cfg.hbm_pages, host_pages=cfg.host_pages,
            dtype=mc.dtype, device=self.device)
        self.requests: Dict[int, Request] = {}
        self.finished: Dict[int, Request] = {}
        self.wait_queue: Deque[int] = deque()
        self.step_count = 0
        self.eviction = make_eviction_policy(cfg.policy)
        # Pluggable scheduling decisions (admission / preemption / decode
        # order / per-step budget split).  A FRESH instance per engine —
        # stateful policies (DRR deficits) must not bleed across replicas.
        self.scheduler = make_scheduler_policy(cfg.scheduler)
        # Prefix-cache chains matched at admission for requests still in
        # ``prefilling`` state — insertion into the cache happens only once
        # the whole prompt is ingested.
        self._pending_chains: Dict[int, list] = {}
        # Reserve one HBM slot as the write target for inactive batch rows,
        # so the batched scatter never collides with a real page.
        self.scratch_slot = self.pool.free_hbm.pop(0)
        # Cross-request prefix sharing: the radix cache itself, plus (under
        # the guided policy) a SECOND GuidanceRuntime whose tier objects are
        # the shared prefixes — per-interval hit counts as the access
        # profile, ski-rental promote/demote, batched-exchange enforcement.
        self.prefix_cache: Optional[PrefixCache] = None
        self.prefix_backend: Optional[PrefixBackend] = None
        self.prefix_runtime: Optional[GuidanceRuntime] = None
        if cfg.enable_prefix_cache:
            self.prefix_cache = PrefixCache(
                self.pool, cfg.page_size, min_pages=cfg.min_prefix_pages)
        # Guided expert-weight tiering: the ExpertStore exists whenever
        # expert weights are off-chip (LRU demand management works under
        # any policy); the third GuidanceRuntime rides only under gdt,
        # like the KV and prefix controllers.
        self.expert_store: Optional[ExpertStore] = None
        self.expert_backend: Optional[ExpertBackend] = None
        self.expert_runtime: Optional[GuidanceRuntime] = None
        if cfg.expert_offchip:
            E = self.model.moe_cfg.padded_experts
            moe_params = params["layers"]["moe"]
            # Overlap window for the modeled prefetch clock: the attention
            # weight bytes each layer reads before the next FFN needs its
            # experts.
            attn_bytes = (4 * mc.d_model * model.attn_cfg.n_heads
                          * model.attn_cfg.head_dim
                          * moe_params["w_gate"].dtype.itemsize)
            self.expert_store = ExpertStore(
                moe_params, mc.n_layers, E,
                cfg.expert_cache_size or mc.n_layers * E,
                double_buffer=cfg.expert_double_buffer, hw=hw,
                window_bytes=attn_bytes, device=self.device)
            # The dense expert stacks live in the store's tiers now; serve
            # against a param view holding only the router.  ``params``
            # itself is untouched (replicas re-derive their own stores);
            # a caller that needs the HBM back deletes its expert arrays.
            layers = dict(params["layers"])
            layers["moe"] = {"router": moe_params["router"]}
            params = {**params, "layers": layers}
        # A no-op for arrays already on this device; a replica on another
        # chip gets its own copy there.
        self.params = jax.device_put(params, self.device)
        self.kv_backend: Optional[PagedKVBackend] = None
        self.runtime: Optional[GuidanceRuntime] = None
        if cfg.policy == "gdt":
            self.kv_backend = PagedKVBackend(
                self.pool, self.requests, clock=lambda: self.step_count)
            self.runtime = GuidanceRuntime(
                self.kv_backend, hw,
                GuidanceConfig(
                    strategy=cfg.strategy,
                    # The reserved scratch slot is not placeable capacity.
                    fast_capacity_bytes=(cfg.hbm_pages - 1) * self.pool.page_bytes,
                    interval_steps=cfg.interval_steps,
                    decay=cfg.access_decay,
                    num_fragments=cfg.num_fragments,
                    skip_empty_intervals=True),
                clock=lambda: self.step_count)
            if self.prefix_cache is not None:
                self.prefix_backend = PrefixBackend(
                    self.prefix_cache, clock=lambda: self.step_count)
                self.prefix_runtime = GuidanceRuntime(
                    self.prefix_backend, hw,
                    GuidanceConfig(
                        strategy=cfg.strategy,
                        fast_capacity_bytes=(cfg.hbm_pages - 1)
                        * self.pool.page_bytes,
                        interval_steps=cfg.interval_steps,
                        decay=cfg.access_decay,
                        num_fragments=cfg.num_fragments,
                        skip_empty_intervals=True),
                    clock=lambda: self.step_count)
            if self.expert_store is not None:
                self.expert_backend = ExpertBackend(
                    self.expert_store, clock=lambda: self.step_count)
                self.expert_runtime = GuidanceRuntime(
                    self.expert_backend, hw,
                    GuidanceConfig(
                        strategy=cfg.strategy,
                        fast_capacity_bytes=self.expert_store.cache_bytes,
                        interval_steps=cfg.interval_steps,
                        decay=cfg.access_decay,
                        num_fragments=cfg.num_fragments,
                        skip_empty_intervals=True),
                    clock=lambda: self.step_count)
        self._decode_greedy = _jit(self._build_decode(with_sampler=False))
        self._decode_sampled = _jit(self._build_decode(with_sampler=True))
        self._prefill = _jit(self._build_prefill())
        if self.expert_store is not None:
            self._build_tiered_closures()
        self.last_logits: Dict[int, np.ndarray] = {}
        # --------------------------------------------------- counters
        self.swap_in_events = 0
        self.prefill_dispatches = 0    # jitted dispatches spent on prefill
        self.prefill_tokens = 0        # prompt tokens ingested
        self.prefill_chunks = 0        # interleaved chunk dispatches
        self.admissions = 0
        # Sum over admissions of (admit step - queued step); the mean rides
        # in stats() as ``mean_admission_wait_steps``.
        self.admission_wait_steps = 0
        self.preemptions = 0           # paused requests evicted wholesale
        self.starved_steps = 0         # request-steps skipped for capacity
        self.truncations = 0           # requests finished early for capacity
        self.saved_prefill_tokens = 0  # prompt tokens served from the cache
        # Per-finish_reason totals (monotonic — surviving pop_finished
        # drains), reported through stats() and serving_summary.
        self.finish_counts: Dict[str, int] = {
            "stop": 0, "length": 0, "truncated": 0, "cancelled": 0}

    # ------------------------------------------------- telemetry shims
    @property
    def decisions(self):
        """Deprecated: ski-rental decisions now live on the runtime's
        event stream (``engine.runtime.events``)."""
        return self.runtime.decisions if self.runtime is not None else []

    @property
    def last_recs(self) -> Dict[int, bool]:
        """Latest planned placement across BOTH controllers (per-request KV
        pages and shared prefixes) — what guided eviction consults.  Page
        ids are globally unique, so the merge cannot collide."""
        recs: Dict[int, bool] = {}
        if self.kv_backend is not None:
            recs.update(self.kv_backend.last_recs)
        if self.prefix_backend is not None:
            recs.update(self.prefix_backend.last_recs)
        return recs

    @property
    def usable_hbm_pages(self) -> int:
        return self.cfg.hbm_pages - 1          # minus the scratch slot

    def free_logical_pages(self) -> int:
        """Unallocated pages across both tiers — what admission/allocation
        budgets against."""
        return len(self.pool.free_hbm) + len(self.pool.free_host)

    def queue_delay_estimate(self) -> float:
        """Deterministic estimate (in engine steps) of how long a NEW
        request would wait before decoding: the un-ingested prompt-token
        backlog (waiting + mid-prefill) over the per-step prefill capacity,
        plus current decode occupancy.  The Router's dispatch key — a
        replica stuffed with queued 32k prompts now repels new work even
        while its pages-in-use still look modest."""
        backlog = 0
        n_active = 0
        for r in self.requests.values():
            if r.state == "waiting":
                backlog += max(len(r.context) - 1, 1)
            elif r.state == "prefilling":
                backlog += max(len(r.context) - 1 - r.pos, 0)
            elif r.state == "active":
                n_active += 1
        per_step = self.scheduler.step_budget(self).prefill_tokens
        if per_step <= 0:
            # Eager prefill ingests a whole prompt per admission; the
            # page-sized batch capacity is the natural per-step unit.
            per_step = self.cfg.page_size * self.cfg.max_batch
        return backlog / per_step + n_active / self.cfg.max_batch

    # ================================================== shared layer body
    def _layer_body(self, lp, x, kp, vp, *, positions, write_slot,
                    write_off, row_mask, lane_mask, rows, unrows, attend):
        """ONE transformer layer body shared by the jitted decode and
        one-shot prefill closures — a single definition is what keeps
        one-shot prefill bitwise-equal to decode (the invariant
        preemption-by-recompute losslessness rests on).

        The two paths differ only in where the row axis lives (decode:
        batch of B single-token rows, x (B,1,d); prefill: one sequence of
        S token rows, x (1,S,d)) and in the attention call.  ``rows``
        flattens a (.., ., H, dh) projection to (R, H, dh), ``unrows``
        lifts an (R, d) result back to x's layout, ``attend(q, kp, vp)``
        returns (R, H, dh).  Masked rows scatter zeros to the reserved
        scratch slot and carry zero residuals — deterministic, never
        garbage.
        """
        x, h2, kp, vp = self._attn_half(
            lp, x, kp, vp, positions=positions, write_slot=write_slot,
            write_off=write_off, row_mask=row_mask, lane_mask=lane_mask,
            rows=rows, unrows=unrows, attend=attend)
        x = self._ffn_half(lp, x, h2, lane_mask)
        return x, kp, vp

    def _attn_half(self, lp, x, kp, vp, *, positions, write_slot, write_off,
                   row_mask, lane_mask, rows, unrows, attend):
        """Attention through the pre-FFN rmsnorm.  Split from
        ``_layer_body`` so the tiered expert path (expert_offchip) can run
        the identical ops up to the router, sync the routing picks to the
        host, and resume with ``_ffn_half``'s math against cache slots —
        the split point changes WHERE the jit boundary falls, never which
        ops run (on the CPU the outputs are bitwise-equal; on the TPU the
        two programs round differently)."""
        acfg = self.model.attn_cfg
        h = rmsnorm(lp["ln1"], x)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wq"])
        k1 = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"])
        v1 = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"])
        q = rope(q, positions, acfg.rope_theta)
        k1 = rope(k1, positions, acfg.rope_theta)
        m = row_mask[:, None, None]
        kp = kp.at[write_slot, write_off].set(
            jnp.where(m, rows(k1), 0).astype(kp.dtype))
        vp = vp.at[write_slot, write_off].set(
            jnp.where(m, rows(v1), 0).astype(vp.dtype))
        o = attend(rows(q), kp, vp)                      # (R, H, dh)
        y = jnp.einsum("rhk,hkd->rd",
                       o.reshape(o.shape[0], acfg.n_heads, acfg.head_dim),
                       lp["attn"]["wo"])
        x = x + jnp.where(lane_mask, unrows(y), 0)
        h2 = rmsnorm(lp["ln2"], x)
        return x, h2, kp, vp

    def _ffn_half(self, lp, x, h2, lane_mask):
        """FFN + residual, the second half of ``_layer_body``."""
        model = self.model
        if model.cfg.family == "moe":
            # Same dropless routing + grouped GEMM as model.prefill, so a
            # token's expert assignment never depends on how the stream is
            # chunked or batched.
            d = moe_decode(lp["moe"], h2, model.moe_cfg)
        else:
            d = mlp(lp["mlp"], h2)
        return x + jnp.where(lane_mask, d, 0)

    # ========================================================= jit decode
    def _build_decode(self, with_sampler: bool):
        """Two jitted variants share one body: the greedy variant's epilogue
        is a plain ``argmax`` (bitwise the pre-sampling engine, zero
        sampling overhead on the default path); the sampled variant runs
        the full in-dispatch sampler.  They agree bitwise on greedy rows
        (the sampler short-circuits ``temperature<=0`` to the same argmax),
        so the scheduler picks per batch and each compiles only on first
        use — a pure-greedy workload never compiles the sampled path."""
        model = self.model
        acfg = model.attn_cfg
        from ..kernels.ops import paged_attention, sample_tokens

        def step(params, k_pool, v_pool, tokens, page_table, lengths,
                 write_slot, write_off, active, seeds, temperature, top_k,
                 top_p):
            """tokens: (B,1); page_table: (B,MP) HBM slots or -1;
            lengths: (B,) incl. new token; write_slot/off: (B,) where the
            new token's KV goes; active: (B,) bool — inactive rows are
            masked to deterministic zeros rather than carrying garbage;
            seeds/temperature/top_k/top_p: (B,) per-request sampling knobs
            (the sampler runs INSIDE this dispatch, with the next token's
            stream position ``lengths`` as the PRNG fold — the replay
            contract)."""
            x = jnp.take(params["embed"]["tok"], tokens, axis=0)  # (B,1,d)

            def body(carry, xs):
                lp, kp, vp = xs          # kp/vp: (N,P,K,dh)
                x, kp, vp = self._layer_body(
                    lp, carry, kp, vp,
                    positions=(lengths - 1)[:, None],
                    write_slot=write_slot, write_off=write_off,
                    row_mask=active, lane_mask=active[:, None, None],
                    rows=lambda t: t[:, 0], unrows=lambda y: y[:, None],
                    attend=lambda q, kp, vp: paged_attention(
                        q, kp, vp, page_table, lengths, window=acfg.window))
                return x, (kp, vp)

            x, (nk, nv) = jax.lax.scan(
                body, x, (params["layers"], k_pool, v_pool))
            x = rmsnorm(params["final_ln"], x)
            logits = lm_head(params["head"], x)[:, 0]
            logits = jnp.where(active[:, None], logits, 0.0)
            if with_sampler:
                next_tokens = sample_tokens(logits, seeds, lengths,
                                            temperature, top_k, top_p)
            else:
                next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, next_tokens, nk, nv

        return step

    # ======================================================== jit prefill
    def _build_prefill(self):
        """One-shot prompt ingestion: a single jitted call writes S tokens'
        K/V into their page-table slots and attends with per-token causal
        lengths via ``ops.paged_prefill`` — the same layer body decode
        runs, so the result is bitwise-equal to chunked ingestion."""
        model = self.model
        acfg = model.attn_cfg
        from ..kernels.ops import paged_prefill

        def prefill(params, k_pool, v_pool, tokens, page_table, slots, offs,
                    n_real, start):
            """tokens: (S,) padded suffix; page_table: (MP,) the request's
            pages (cache-covered prefix included); slots/offs: (S,) physical
            write target per token (the scratch slot for padded rows);
            n_real: () int32 live rows; start: () int32 absolute position of
            row 0 (0 for an uncached prompt, the covered token count after a
            prefix-cache hit — traced, so hits never recompile).  Rows
            attend by ABSOLUTE length over the page table, so a suffix-only
            dispatch replays the whole-prompt computation bitwise."""
            S = tokens.shape[0]
            local = jnp.arange(S, dtype=jnp.int32)
            positions = start + local
            valid = local < n_real
            lengths = jnp.where(valid, positions + 1, 0)
            x = jnp.take(params["embed"]["tok"], tokens[None], axis=0)

            def body(carry, xs):
                lp, kp, vp = xs
                x, kp, vp = self._layer_body(
                    lp, carry, kp, vp,
                    positions=positions[None],
                    write_slot=slots, write_off=offs,
                    row_mask=valid, lane_mask=valid[None, :, None],
                    rows=lambda t: t[0], unrows=lambda y: y[None],
                    attend=lambda q, kp, vp: paged_prefill(
                        q, kp, vp, page_table, lengths, window=acfg.window))
                return x, (kp, vp)

            _, (nk, nv) = jax.lax.scan(
                body, x, (params["layers"], k_pool, v_pool))
            return nk, nv

        return prefill

    # ==================================================== tiered expert path
    def _build_tiered_closures(self):
        """Jitted pieces of the layer-by-layer pipeline that serves MoE
        FFN weights out of the bounded HBM expert cache
        (``ServeConfig.expert_offchip``).

        The resident path runs one jitted scan over all layers; the tiered
        path cannot (each layer's routed expert set must reach the host so
        the store can ensure residency before the grouped GEMM), so the
        SAME layer ops are recomposed as: per-layer jitted
        attention+router (``_attn_half`` + ``route_tokens``), a host sync
        of the picks, then a jitted FFN-from-cache + residual
        (``apply_dropless_flat`` with the slot map as ``group_experts``).
        Every op and its order is identical to the resident scan — only
        the jit boundaries move — which the bitwise parity tests pin on
        the CPU (on the TPU the two programs round differently, so there
        the tiered result is pinned across cache sizes instead).  While
        one layer's FFN dispatch is in flight the store stages the next
        layer's predicted experts (double buffer).
        """
        model = self.model
        acfg = model.attn_cfg
        mcfg = model.moe_cfg
        from ..kernels.ops import paged_attention, paged_prefill, sample_tokens

        def slice_layer(tree, l):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, l, 0, keepdims=False), tree)

        def embed(params, tokens):
            return jnp.take(params["embed"]["tok"], tokens, axis=0)

        def decode_attn(params, k_pool, v_pool, x, l, page_table, lengths,
                        write_slot, write_off, active):
            lp = slice_layer(params["layers"], l)
            kp = jax.lax.dynamic_index_in_dim(k_pool, l, 0, keepdims=False)
            vp = jax.lax.dynamic_index_in_dim(v_pool, l, 0, keepdims=False)
            x, h2, kp, vp = self._attn_half(
                lp, x, kp, vp, positions=(lengths - 1)[:, None],
                write_slot=write_slot, write_off=write_off,
                row_mask=active, lane_mask=active[:, None, None],
                rows=lambda t: t[:, 0], unrows=lambda y: y[:, None],
                attend=lambda q, kp, vp: paged_attention(
                    q, kp, vp, page_table, lengths, window=acfg.window))
            B, S, d = h2.shape
            gates, experts = route_tokens(
                lp["moe"]["router"], h2.reshape(B * S, d), mcfg)
            k_pool = jax.lax.dynamic_update_index_in_dim(k_pool, kp, l, 0)
            v_pool = jax.lax.dynamic_update_index_in_dim(v_pool, vp, l, 0)
            return x, h2, gates, experts, k_pool, v_pool

        def prefill_prologue(params, tokens, n_real, start):
            S = tokens.shape[0]
            local = jnp.arange(S, dtype=jnp.int32)
            positions = start + local
            valid = local < n_real
            lengths = jnp.where(valid, positions + 1, 0)
            x = jnp.take(params["embed"]["tok"], tokens[None], axis=0)
            return x, positions, lengths, valid

        def prefill_attn(params, k_pool, v_pool, x, l, page_table, slots,
                         offs, positions, lengths, valid):
            lp = slice_layer(params["layers"], l)
            kp = jax.lax.dynamic_index_in_dim(k_pool, l, 0, keepdims=False)
            vp = jax.lax.dynamic_index_in_dim(v_pool, l, 0, keepdims=False)
            x, h2, kp, vp = self._attn_half(
                lp, x, kp, vp, positions=positions[None],
                write_slot=slots, write_off=offs,
                row_mask=valid, lane_mask=valid[None, :, None],
                rows=lambda t: t[0], unrows=lambda y: y[None],
                attend=lambda q, kp, vp: paged_prefill(
                    q, kp, vp, page_table, lengths, window=acfg.window))
            B, S, d = h2.shape
            gates, experts = route_tokens(
                lp["moe"]["router"], h2.reshape(B * S, d), mcfg)
            k_pool = jax.lax.dynamic_update_index_in_dim(k_pool, kp, l, 0)
            v_pool = jax.lax.dynamic_update_index_in_dim(v_pool, vp, l, 0)
            return x, h2, gates, experts, k_pool, v_pool

        def ffn(x, h2, wg, wu, wd, slot_map, gates, experts, lane_mask):
            d = apply_dropless_flat(gates, experts, h2, wg, wu, wd, mcfg,
                                    expert_slots=slot_map)
            return x + jnp.where(lane_mask, d, 0)

        def spec_route(params, x, nl):
            # Speculative gating: layer ``nl``'s own pre-FFN norm + router
            # applied to the residual stream as it leaves layer nl-1 —
            # i.e. one attention delta early.  The residual stream
            # dominates the router input, so these probabilities forecast
            # the next dispatch's working set; the store stages the top of
            # the ranking while this layer's grouped GEMM is in flight.
            # Full (T, E) probs, not top-k picks: borderline tokens flip
            # their picks under the missing attention delta, but the mass
            # ranking is stabler than any single pick.  Predictions never
            # touch results — a miss means a blocking demand fetch.
            lp = slice_layer(params["layers"], nl)
            h2 = rmsnorm(lp["ln2"], x)
            B, S, d = h2.shape
            logits = jnp.einsum("td,de->te",
                                h2.reshape(B * S, d).astype(jnp.float32),
                                lp["moe"]["router"])
            return jax.nn.softmax(logits, axis=-1)

        def make_tail(with_sampler):
            def tail(params, x, lengths, active, seeds, temperature, top_k,
                     top_p):
                x = rmsnorm(params["final_ln"], x)
                logits = lm_head(params["head"], x)[:, 0]
                logits = jnp.where(active[:, None], logits, 0.0)
                if with_sampler:
                    next_tokens = sample_tokens(logits, seeds, lengths,
                                                temperature, top_k, top_p)
                else:
                    next_tokens = jnp.argmax(logits, axis=-1).astype(
                        jnp.int32)
                return logits, next_tokens
            return tail

        self._t_embed = _jit(embed)
        self._t_spec_route = _jit(spec_route)
        self._t_decode_attn = _jit(decode_attn)
        self._t_prefill_prologue = _jit(prefill_prologue)
        self._t_prefill_attn = _jit(prefill_attn)
        self._t_ffn = _jit(ffn)
        self._t_tail_greedy = _jit(make_tail(False))
        self._t_tail_sampled = _jit(make_tail(True))

    def _tiered_ffn(self, l, x, h2, gates, experts, lane_mask, token_mask,
                    wrap_prefetch=True):
        """Host half of one tiered layer: sync the routed picks, make them
        resident (commit the in-flight prefetch / demand-fetch misses /
        LRU-evict), dispatch the FFN against cache slots, then put the
        NEXT layer's predicted experts in flight while this FFN runs.

        ``token_mask`` (host bool, one per routed token) excludes padded
        prefill rows and inactive batch rows from the residency working
        set: their FFN outputs are zeroed by ``lane_mask`` in BOTH paths
        and the dropless dispatch is per-row independent, so their picks
        may legally hit absent experts (slot −1) without affecting any
        live row's bits — and must not inflate the cache requirement or
        the access profile."""
        store = self.expert_store
        E = self.model.moe_cfg.padded_experts
        counts = np.bincount(
            np.asarray(experts).reshape(len(token_mask), -1)
            [token_mask].reshape(-1), minlength=E)
        slot_map = store.dispatch(l, counts, self.step_count)
        nb = store.take_rental_bytes()
        if nb and self.expert_runtime is not None:
            self.expert_runtime.record_rental(nb, source="expert_miss")
        # Experts that only masked rows route to have no slot (-1); point
        # them at row 0: those rows' outputs are zeroed by lane_mask, and a
        # -1 would be an out-of-bounds weight DMA (the TPU halts on it).
        slots = jnp.asarray(np.maximum(slot_map, 0))
        x = self._t_ffn(x, h2, store.w_gate_cache, store.w_up_cache,
                        store.w_down_cache, slots, gates, experts, lane_mask)
        # Double buffer: stage the next dispatch's predicted working set
        # while this one's grouped GEMM is in flight.  Within a step the
        # prediction is speculative gating — the next layer's router run
        # on the residual stream one attention delta early; at the
        # wrap-around the next step's first dispatch routes a token that
        # does not exist yet, so the store falls back to recency + the
        # guidance profile.
        nl = (l + 1) % self.model.cfg.n_layers
        if nl:
            self._spec_prefetch(nl, x, token_mask)
        elif wrap_prefetch:
            store.prefetch(0, self.step_count)
        return x

    def _spec_prefetch(self, l, x, token_mask):
        """Forecast layer ``l``'s routed experts from the residual stream
        ``x`` (speculative gating) and put the likeliest non-resident ones
        in flight.  Live rows only — masked rows route garbage by design
        (see ``_tiered_ffn``).  The forecast covers the rows' top-k picks
        plus a small margin of next-likeliest experts by router mass: the
        margin costs overlapped (hidden) bytes and buys back the picks
        the missing attention delta flips."""
        mcfg = self.model.moe_cfg
        live = int(np.sum(token_mask))
        if not live:
            return
        probs = np.asarray(self._t_spec_route(self.params, x, jnp.int32(l)))
        mass = probs[np.asarray(token_mask)].sum(axis=0)
        order = np.argsort(-mass, kind="stable")
        cap = min(mcfg.padded_experts, live * mcfg.top_k + 2)
        self.expert_store.prefetch(
            l, self.step_count, predicted=[int(e) for e in order[:cap]])

    def _tiered_decode(self, tokens, page_table, lengths, write_slot,
                       write_off, active, seeds, temperature, top_k, top_p,
                       use_sampler):
        x = self._t_embed(self.params, tokens)
        kq, vq = self.pool.k_hbm, self.pool.v_hbm
        lane = active[:, None, None]
        mask = np.asarray(active)
        for l in range(self.model.cfg.n_layers):
            x, h2, gates, experts, kq, vq = self._t_decode_attn(
                self.params, kq, vq, x, jnp.int32(l), page_table, lengths,
                write_slot, write_off, active)
            x = self._tiered_ffn(l, x, h2, gates, experts, lane, mask,
                                 wrap_prefetch=False)
        tail = self._t_tail_sampled if use_sampler else self._t_tail_greedy
        logits, next_tokens = tail(self.params, x, lengths, active, seeds,
                                   temperature, top_k, top_p)
        # The sampled token IS the next step's layer-0 residual stream
        # (x = embed(token)), so the one dispatch the in-step speculation
        # cannot see — the wrap-around to the next step's first layer —
        # gets its own forecast here, hiding the fetch under the tail +
        # host scheduling gap.  Batch rotation between steps makes this a
        # forecast, not an oracle; mispredictions demand-fetch as usual.
        if self.expert_store.double_buffer:
            self._spec_prefetch(
                0, self._t_embed(self.params, next_tokens[:, None]), mask)
        return logits, next_tokens, kq, vq

    def _tiered_prefill(self, tokens, page_table, slots, offs, n_real,
                        start):
        x, positions, lengths, valid = self._t_prefill_prologue(
            self.params, tokens, n_real, start)
        kq, vq = self.pool.k_hbm, self.pool.v_hbm
        lane = valid[None, :, None]
        mask = np.asarray(valid)
        for l in range(self.model.cfg.n_layers):
            x, h2, gates, experts, kq, vq = self._t_prefill_attn(
                self.params, kq, vq, x, jnp.int32(l), page_table, slots,
                offs, positions, lengths, valid)
            x = self._tiered_ffn(l, x, h2, gates, experts, lane, mask)
        return kq, vq

    def _run_prefill(self, tokens, page_table, slots, offs, n_real, start):
        """One bucketed prefill dispatch: the resident single-jit scan, or
        the layer-by-layer tiered pipeline when expert weights are
        off-chip.  Both return the updated (nk, nv) pools."""
        args = (jnp.asarray(tokens), jnp.asarray(page_table),
                jnp.asarray(slots), jnp.asarray(offs), jnp.int32(n_real),
                jnp.int32(start))
        if self.expert_store is not None:
            return self._tiered_prefill(*args)
        return self._prefill(self.params, self.pool.k_hbm, self.pool.v_hbm,
                             *args)

    # ========================================================== requests
    def add_request(self, request_id: int, prompt: List[int],
                    max_new: Optional[int] = None,
                    params: Optional[SamplingParams] = None) -> None:
        """Validate and enqueue; admission happens immediately if the pool
        has room, else at a later ``step()``.  ``params`` carries the
        request's sampling/stop behaviour.  The generation budget resolves
        HERE and nowhere else: ``params.max_tokens`` when set, else
        ``max_new``, else ``DEFAULT_MAX_TOKENS``."""
        if request_id in self.requests or request_id in self.finished:
            raise ValueError(f"duplicate request_id {request_id}")
        if params is None:
            params = SamplingParams()
        if params.max_tokens is not None:
            max_new = params.max_tokens
        elif max_new is None:
            max_new = DEFAULT_MAX_TOKENS
        self._validate_budget(request_id, prompt, max_new)
        req = Request(request_id=request_id, tokens=list(prompt),
                      max_new=max_new, params=params,
                      queued_step=self.step_count)
        self.requests[request_id] = req
        self.wait_queue.append(request_id)
        self._admit_waiting()

    def _validate_budget(self, request_id: int, prompt: Sequence[int],
                         max_new: int) -> None:
        """Reject requests that can NEVER run on this engine, with an error
        naming the knob (shared by ``add_request`` and the migration import
        path — a ticket must clear the same bars as a fresh submit)."""
        if not prompt:
            raise ValueError("empty prompt")
        P = self.cfg.page_size
        MP = self.cfg.max_pages_per_seq
        total_tokens = len(prompt) - 1 + max_new   # tokens written to KV
        if total_tokens > MP * P:
            raise ValueError(
                f"request {request_id} needs {total_tokens} KV tokens "
                f"({len(prompt)} prompt + {max_new} new) but "
                f"max_pages_per_seq={MP} * page_size={P} caps a sequence at "
                f"{MP * P}; raise ServeConfig.max_pages_per_seq or shorten "
                f"the request")
        prompt_pages = -(-max(len(prompt) - 1, 1) // P)
        lifetime_pages = -(-total_tokens // P)
        if min(prompt_pages + 1, lifetime_pages) > self.usable_hbm_pages:
            raise ValueError(
                f"request {request_id}'s prompt needs {prompt_pages} pages "
                f"(+1 to decode) but only {self.usable_hbm_pages} usable "
                f"HBM pages exist (hbm_pages={self.cfg.hbm_pages} minus the "
                f"scratch slot); raise ServeConfig.hbm_pages")

    # ------------------------------------------------- live migration
    def export_request(self, request_id: int) -> RequestTicket:
        """Snapshot a live request's serializable identity (see
        ``RequestTicket``): what another engine needs to continue the
        stream.  Read-only — pairs with ``remove_request`` once the
        handoff lands."""
        req = self.requests.get(request_id)
        if req is None:
            raise ValueError(
                f"cannot export request {request_id}: unknown or finished "
                f"id (finished results hand off as results, not tickets)")
        return RequestTicket(
            request_id=req.request_id, prompt=list(req.tokens),
            max_new=req.max_new, params=req.params,
            generated=list(req.generated))

    def remove_request(self, request_id: int) -> Request:
        """Withdraw a live request wholesale — live migration moved it to
        another engine.  Frees pages and prunes the live tables WITHOUT
        marking the request finished (its stream continues elsewhere);
        stale wait-queue entries self-clean in ``_admit_waiting``."""
        req = self.requests.pop(request_id, None)
        if req is None:
            raise ValueError(
                f"cannot remove request {request_id}: unknown or finished "
                f"id")
        self._release_pages(request_id)
        self._pending_chains.pop(request_id, None)
        self.last_logits.pop(request_id, None)
        return req

    def import_request(self, ticket: RequestTicket,
                       kv: Optional["PageExport"] = None) -> Request:
        """Continue another engine's request on THIS engine.

        Cold path (``kv=None``): rebuild by recompute — the ticket enters
        the wait queue with its generated tokens preloaded, and admission
        re-prefills prompt+generated exactly as a preempted request would
        (bitwise, because one-shot prefill == decode and sampling folds
        absolute positions).  Warm path (``kv`` from the source pool's
        ``export_pages``): re-attach any leading blocks this engine's
        prefix cache already holds (by chain hash — equal token chains mean
        equal keys mean bitwise-equal pages), import the remaining pages
        into the local pool, and resume decoding with zero recompute.  A
        warm import that cannot fit raises ``MemoryError`` with all partial
        state rolled back, so callers can retry cold."""
        rid = ticket.request_id
        if rid in self.requests or rid in self.finished:
            raise ValueError(f"duplicate request_id {rid}")
        self._validate_budget(rid, ticket.prompt, ticket.max_new)
        req = Request(request_id=rid, tokens=list(ticket.prompt),
                      max_new=ticket.max_new, params=ticket.params,
                      generated=list(ticket.generated),
                      queued_step=self.step_count)
        if kv is None:
            self.requests[rid] = req
            self.wait_queue.append(rid)
            self._admit_waiting()
            return req
        context = req.context
        n_ingest = len(context) - 1
        self.requests[rid] = req
        try:
            chain = []
            if self.prefix_cache is not None:
                chain = self.prefix_cache.match(
                    context[:n_ingest], self.step_count, count=False)
                hit_ids = [n.page_id for n in chain]
                missing = [pid for pid in hit_ids
                           if self.pool.pages[pid].hbm_slot is None]
                if missing:
                    self._ensure_free_hbm(len(missing), needed=hit_ids)
                    self.pool.swap_in_many(missing)
                for node in chain:
                    self.pool.attach(rid, node.page_id, self.step_count)
            self.pool.import_pages(kv.select_from(len(chain)), rid,
                                   self.step_count)
        except MemoryError:
            self._release_pages(rid)
            self.requests.pop(rid, None)
            raise
        req.pos = n_ingest
        req.state = "active"
        req.last_scheduled = self.step_count
        # Adopt the migrated prompt's full-page blocks into the local cache
        # under their (identical) chain hashes, so sharing survives the
        # membership change on the destination replica.
        self._insert_prefix(req, context, n_ingest, chain)
        return req

    # The explicit lifecycle contract (DESIGN.md §7): transitions outside
    # it raise a named ValueError instead of silently mutating queue state.
    #   pause:  active -> paused; paused -> no-op (idempotent);
    #           anything else raises.
    #   resume: paused -> active; preempted -> waiting (re-enqueue);
    #           active/waiting -> no-op (already running / already queued);
    #           finished or unknown ids raise.
    def _lookup(self, request_id: int, verb: str) -> Request:
        req = self.requests.get(request_id)
        if req is None:
            if request_id in self.finished:
                raise ValueError(
                    f"cannot {verb} request {request_id}: already finished "
                    f"(drain the result with pop_finished)")
            raise ValueError(
                f"cannot {verb} request {request_id}: unknown id")
        return req

    def pause(self, request_id: int):
        req = self._lookup(request_id, "pause")
        if req.state == "paused":
            return                       # idempotent
        if req.state != "active":
            raise ValueError(
                f"cannot pause request {request_id} in state "
                f"{req.state!r}: only active requests pause (a "
                f"{req.state} request holds no schedulable position)")
        req.state = "paused"

    def resume(self, request_id: int):
        req = self._lookup(request_id, "resume")
        if req.state in ("active", "waiting"):
            return                       # idempotent / already queued
        if req.state == "paused":
            req.state = "active"
        elif req.state == "preempted":
            # Pages were dropped; re-prefill via the admission path (exact:
            # one-shot prefill == decode bitwise, and sampling folds the
            # absolute stream position, so replay resamples identically).
            req.state = "waiting"
            req.queued_step = self.step_count
            self.wait_queue.append(request_id)
            self._admit_waiting()

    def cancel(self, request_id: int) -> Request:
        """Withdraw a live request in ANY state (waiting, prefilling,
        active, paused, preempted): pages free immediately, the stale
        wait-queue entry self-cleans at the next admission sweep, and the
        result parks in ``finished`` with ``finish_reason="cancelled"``
        (tokens generated so far are kept).  Finished or unknown ids raise
        the usual named ``ValueError``."""
        req = self._lookup(request_id, "cancel")
        self._finish(req, reason="cancelled")
        return req

    def pop_finished(self, request_id: Optional[int] = None):
        """Drain finished requests (all, or one) so long-lived engines do
        not accumulate results forever."""
        if request_id is not None:
            return self.finished.pop(request_id)
        out, self.finished = self.finished, {}
        return out

    # ------------------------------------------------------- admission
    def _admit_waiting(self):
        """Policy-ordered admission: admit the policy's head while its
        (re-)prefill pages fit the free logical capacity, preempting
        paused requests' pages when that unblocks the head.  Admission
        never skips past a head that does not fit (bounded head-of-line
        blocking is what keeps every policy starvation-free)."""
        P = self.cfg.page_size
        while self.wait_queue:
            head = self.requests.get(self.wait_queue[0])
            if head is None or head.state != "waiting":  # cancelled/stale
                self.wait_queue.popleft()
                continue
            waiting = [r for r in (self.requests.get(rid)
                                   for rid in self.wait_queue)
                       if r is not None and r.state == "waiting"]
            req = self.scheduler.admission_order(waiting, self)[0]
            n_ingest = len(req.context) - 1
            n_pages = -(-n_ingest // P) if n_ingest else 0
            remaining = req.max_new - len(req.generated)
            pages_total = -(-(n_ingest + remaining) // P)
            if min(n_pages + 1, pages_total) > self.usable_hbm_pages:
                # A preempted request whose regenerated context outgrew the
                # fast tier can never decode again: finish it, don't wedge
                # the queue head forever.
                self.wait_queue.remove(req.request_id)
                self._finish(req, reason="truncated")
                continue
            # Admit with one page of growth slack (capped at the request's
            # real lifetime need), so an admitted request can always decode
            # at least a page's worth before capacity pressure returns.
            if min(n_pages + 1, pages_total) > self.free_logical_pages():
                # Cold cached prefixes yield their logical pages before any
                # live request is preempted.
                shortfall = (min(n_pages + 1, pages_total)
                             - self.free_logical_pages())
                if (self.prefix_cache is not None
                        and self.prefix_cache.reclaim(shortfall)):
                    continue
                if not self._preempt_one():
                    return              # head waits; order preserved
                continue
            self.wait_queue.remove(req.request_id)
            self.admissions += 1
            self.admission_wait_steps += self.step_count - req.queued_step
            self._admit(req)

    def _admit(self, req: Request) -> None:
        """Move an admitted request out of the wait queue: straight to
        ``active`` via eager whole-suffix prefill, or — with
        chunked-prefill interleaving on — into ``prefilling``, where
        ``_advance_prefills`` ingests budgeted chunks each step."""
        budget = self.scheduler.step_budget(self)
        if self.cfg.prefill == "one_shot" and budget.prefill_tokens > 0:
            self._begin_prefill(req)
        else:
            self._prefill_request(req)
            req.state = "active"
        req.last_scheduled = self.step_count

    def _begin_prefill(self, req: Request) -> None:
        """Start an interleaved prefill: consult the prefix cache, allocate
        the WHOLE uncovered suffix's pages now (admission already budgeted
        them — allocating lazily per chunk could lose the race against
        later admissions), and park the request in ``prefilling`` state.
        Trivial ingests (empty / full cache hit) go straight to active."""
        context = req.context
        n_ingest = len(context) - 1
        if n_ingest == 0:
            req.pos = 0
            req.state = "active"
            return
        P = self.cfg.page_size
        rid = req.request_id
        chain = self._match_prefix(req, context, n_ingest)
        covered = len(chain) * P
        if n_ingest - covered == 0:
            req.pos = n_ingest           # full hit: nothing to dispatch
            req.state = "active"
            return
        n_prefix_pages = covered // P
        n_pages = -(-n_ingest // P) - n_prefix_pages
        self._ensure_free_hbm(
            n_pages, needed=[p.page_id
                             for p in self.pool.request_pages(rid)])
        for idx in range(n_pages):
            self.pool.allocate(rid, n_prefix_pages + idx, self.step_count)
        req.pos = covered
        req.state = "prefilling"
        self._pending_chains[rid] = chain

    def _advance_prefills(self) -> None:
        """Spend this step's prefill token budget across ``prefilling``
        requests in the policy's prefill order; a request whose prompt
        completes joins the decode-eligible actives the same step."""
        prefilling = [r for r in self.requests.values()
                      if r.state == "prefilling"]
        if not prefilling:
            return
        budget = self.scheduler.step_budget(self).prefill_tokens
        if budget <= 0:                  # budget turned off mid-flight:
            budget = float("inf")        # drain rather than wedge forever
        for req in self.scheduler.prefill_order(prefilling, self):
            if budget <= 0:
                break
            n_ingest = len(req.context) - 1
            n = int(min(budget, n_ingest - req.pos))
            self._prefill_chunk(req, n)
            budget -= n
            self.scheduler.on_tokens(req, n, self)
            if req.pos >= n_ingest:
                chain = self._pending_chains.pop(req.request_id, [])
                self._insert_prefix(req, req.context, n_ingest, chain)
                req.state = "active"
                req.last_scheduled = self.step_count

    def _prefill_chunk(self, req: Request, n: int) -> None:
        """Ingest ``req.context[req.pos : req.pos+n]`` with one bucketed
        dispatch at absolute start ``req.pos`` — the same jitted closure as
        one-shot prefill, so every chunking of a prompt produces
        bitwise-identical K/V (rows attend by absolute length over the
        request's full page table)."""
        context = req.context
        P = self.cfg.page_size
        MP = self.cfg.max_pages_per_seq
        rid = req.request_id
        start = req.pos
        my_pages = self.pool.request_pages(rid)
        # The dispatch's table covers every page, so the whole sequence
        # must be HBM-resident (earlier chunks' pages may have been evicted
        # between steps — demand swap-in is a rental like any other).  Same
        # atomic batched exchange as _prepare_batch: evictions and swap-ins
        # stage together, so residency succeeds even when both free lists
        # are empty (an evict-then-swap-in order would need host slots that
        # a tightly-sized pool does not have).
        missing = [p.page_id for p in my_pages if p.hbm_slot is None]
        if missing:
            shortfall = len(missing) - len(self.pool.free_hbm)
            victims: List[int] = []
            if shortfall > 0:
                exclude = {p.page_id for p in my_pages}
                cands = [p for p in self.pool.pages.values()
                         if p.hbm_slot is not None
                         and p.page_id not in exclude]
                victims = self.eviction.pick_many(cands, self, shortfall)
                if len(victims) < shortfall:
                    raise MemoryError("no evictable page")  # unreachable:
            self.pool.exchange(victims, missing)      # chunk pages <= usable
            self._note_swap_in(len(missing))
            my_pages = self.pool.request_pages(rid)
        by_index = {p.index_in_seq: p for p in my_pages}
        S = max(P, 1 << (n - 1).bit_length())
        tokens = np.zeros((S,), np.int32)
        tokens[:n] = context[start:start + n]
        slots = np.full((S,), self.scratch_slot, np.int32)
        offs = np.zeros((S,), np.int32)
        written = set()
        for t in range(n):
            idx, off = divmod(start + t, P)
            page = by_index[idx]
            slots[t] = page.hbm_slot
            offs[t] = off
            page.tokens_used = max(page.tokens_used, off + 1)
            written.add(idx)
        table = np.full((MP,), -1, np.int32)
        for p in my_pages:
            table[p.index_in_seq] = p.hbm_slot
        nk, nv = self._run_prefill(tokens, table, slots, offs, n, start)
        self.pool.k_hbm, self.pool.v_hbm = nk, nv
        req.pos = start + n
        for idx in written:
            if not by_index[idx].shared:
                by_index[idx].accesses += 1   # chunk's write set
        self.prefill_dispatches += 1
        self.prefill_chunks += 1
        self.prefill_tokens += n

    def _preempt_one(self) -> bool:
        """Drop ALL pages of the policy's chosen paused victim (preempt by
        recompute: resume re-prefills prompt+generated)."""
        victims = [r for r in self.requests.values()
                   if r.state == "paused"
                   and self.pool.request_pages(r.request_id)]
        if not victims:
            return False
        victim = self.scheduler.preempt_paused(victims, self)
        self._release_pages(victim.request_id)
        victim.pos = 0
        victim.state = "preempted"
        self.preemptions += 1
        return True

    def _release_pages(self, request_id: int):
        """Drop every page reference the request holds.  Shared prefix
        pages survive on the cache's reference; only pages that actually
        died leave the eviction policy's recommendation view."""
        freed = self.pool.release_request(request_id)
        if self.kv_backend is not None:
            self.kv_backend.forget_pages(freed)

    def _reclaim_logical_pages(self):
        """Nothing schedulable while active requests exist — logical pages
        are exhausted.  Reclaim by preempting a paused page-holder first,
        else the policy's running victim (active or mid-prefill; it
        re-enters the wait queue and recomputes later).  A request that is
        alone against the whole pool can never grow or finish: truncate
        it."""
        if self.prefix_cache is not None and self.prefix_cache.reclaim(1):
            return
        if self._preempt_one():
            return
        cands = [r for r in self.requests.values()
                 if r.state in ("active", "prefilling")]
        holders = [r for r in cands
                   if self.pool.request_pages(r.request_id)]
        if not holders:
            return
        if len(cands) == 1 and holders == cands:
            self._finish(cands[0], reason="truncated")
            return
        victim = self.scheduler.preempt_active(holders, self)
        self._release_pages(victim.request_id)
        victim.pos = 0
        victim.state = "waiting"
        victim.queued_step = self.step_count
        self._pending_chains.pop(victim.request_id, None)
        self.wait_queue.append(victim.request_id)
        self.preemptions += 1

    # -------------------------------------------------------- prefill
    def _match_prefix(self, req: Request, context: List[int],
                      n_ingest: int) -> list:
        """Consult the prefix cache and attach every matched full-page
        block to the request by reference.  Returns the matched node chain
        (empty without a cache or on a miss).  Matched pages are made
        HBM-resident HERE: the suffix dispatch (or first decode step, on a
        full hit) attends over them."""
        if self.prefix_cache is None:
            return []
        chain = self.prefix_cache.match(context[:n_ingest], self.step_count)
        if not chain:
            return []
        hit_ids = [n.page_id for n in chain]
        missing = [pid for pid in hit_ids
                   if self.pool.pages[pid].hbm_slot is None]
        if missing:
            self._ensure_free_hbm(len(missing), needed=hit_ids)
            self.pool.swap_in_many(missing)
            self.swap_in_events += len(missing)
            # A hit on a demoted prefix is a rental payment against the
            # PREFIX controller's ledger (it made the demotion call).
            if self.prefix_runtime is not None:
                self.prefix_runtime.record_rental(
                    self.pool.page_bytes * len(missing),
                    source="prefix_hit")
        for node in chain:
            self.pool.attach(req.request_id, node.page_id, self.step_count)
        self.saved_prefill_tokens += len(chain) * self.cfg.page_size
        return chain

    def _insert_prefix(self, req: Request, context: List[int],
                       n_ingest: int, chain: list) -> None:
        """Adopt the request's freshly written full-page PROMPT blocks into
        the cache (generated tokens never extend a shareable prefix — the
        reuse signal is the shared system prompt, and K/V content is
        token-determined either way).  ``chain`` is what ``_match_prefix``
        already covered; insertion continues the radix walk from there."""
        if self.prefix_cache is None:
            return
        self.prefix_cache.insert(
            context[:n_ingest], self.pool.request_pages(req.request_id),
            limit=min(n_ingest, len(req.tokens)), step=self.step_count,
            chain=chain)

    def _prefill_request(self, req: Request):
        """Ingest ``req.context[:-1]`` (the last token is fed by the first
        decode step).  The prefix cache is consulted first: matched blocks
        attach by reference and only the uncovered suffix is ingested — one
        jitted dispatch in one_shot mode (a FULL hit dispatches nothing);
        the chunked oracle steps suffix tokens through decode."""
        context = req.context
        n_ingest = len(context) - 1
        if n_ingest == 0:
            req.pos = 0
            return
        P = self.cfg.page_size
        rid = req.request_id
        chain = self._match_prefix(req, context, n_ingest)
        covered = len(chain) * P
        n_suffix = n_ingest - covered
        if n_suffix == 0:
            # Full hit: every ingested token is already in shared pages.
            req.pos = n_ingest
            return
        if self.cfg.prefill == "chunked":
            req.pos = covered
            for t in context[covered:-1]:
                self._decode_one(req, t)
            self.prefill_tokens += n_suffix
            self._insert_prefix(req, context, n_ingest, chain)
            return
        MP = self.cfg.max_pages_per_seq
        n_prefix_pages = covered // P
        n_pages = -(-n_ingest // P) - n_prefix_pages
        self._ensure_free_hbm(
            n_pages, needed=[p.page_id
                             for p in self.pool.request_pages(rid)])
        pages = [self.pool.allocate(rid, n_prefix_pages + idx,
                                    self.step_count)
                 for idx in range(n_pages)]
        # Pad the token axis to a power-of-two bucket (>= one page) so jit
        # compiles per bucket, not per suffix length.
        S = max(P, 1 << (n_suffix - 1).bit_length())
        tokens = np.zeros((S,), np.int32)
        tokens[:n_suffix] = context[covered:n_ingest]
        slots = np.full((S,), self.scratch_slot, np.int32)
        offs = np.zeros((S,), np.int32)
        # ``covered`` is page-aligned, so suffix token t lands at page t//P
        # offset t%P of the private tail.
        for t in range(n_suffix):
            slots[t] = pages[t // P].hbm_slot
            offs[t] = t % P
        table = np.full((MP,), -1, np.int32)
        for p in self.pool.request_pages(rid):
            table[p.index_in_seq] = p.hbm_slot
        nk, nv = self._run_prefill(tokens, table, slots, offs, n_suffix,
                                   covered)
        self.pool.k_hbm, self.pool.v_hbm = nk, nv
        req.pos = n_ingest
        for i, p in enumerate(pages):
            p.accesses += 1         # the dispatch's access set: every page
            p.tokens_used = min(P, n_suffix - i * P)
        self.prefill_dispatches += 1
        self.prefill_tokens += n_suffix
        self._insert_prefix(req, context, n_ingest, chain)

    # ------------------------------------------------------- page mgmt
    def _note_swap_in(self, n_pages: int):
        """Demand swap-ins are rental payments; one batched transfer is
        still ``n_pages`` pages of rent."""
        self.swap_in_events += n_pages
        if self.runtime is not None and n_pages:
            self.runtime.record_rental(self.pool.page_bytes * n_pages,
                                       source="swap_in")

    def _page_for_write(self, req: Request) -> Tuple[int, int]:
        """(hbm_slot, offset) for the next token.  The batch-prepare pass
        has already made every page resident and allocated the write page.
        Copy-on-write guard: sharing is full-page granular, so the write
        target is never shared on the normal path (a request's first
        private token lands past the covered prefix on a fresh page) — but
        if a shared page IS the target, the request gets a private copy
        rather than corrupting every other holder's KV."""
        idx, off = divmod(req.pos, self.cfg.page_size)
        page = self.pool.request_pages(req.request_id)[idx]
        if page.refcount > 1 or page.shared:
            page = self.pool.copy_page(page.page_id, req.request_id,
                                       self.step_count)
        page.tokens_used = off + 1
        return page.hbm_slot, off

    def _prepare_batch(self, reqs: List[Request]):
        """Make the whole scheduled batch resident with ONE atomic batched
        exchange (evictions + swap-ins staged together, so it succeeds even
        when both free lists are empty), then allocate write pages."""
        P = self.cfg.page_size
        need_ids: List[int] = []
        missing: List[int] = []
        n_alloc = 0
        for r in reqs:
            pages = self.pool.request_pages(r.request_id)
            need_ids.extend(p.page_id for p in pages)
            missing.extend(p.page_id for p in pages if p.hbm_slot is None)
            if r.pos // P >= len(pages):
                n_alloc += 1
        shortfall = len(missing) + n_alloc - len(self.pool.free_hbm)
        victims: List[int] = []
        if shortfall > 0:
            exclude = set(need_ids)
            cands = [p for p in self.pool.pages.values()
                     if p.hbm_slot is not None and p.page_id not in exclude]
            victims = self.eviction.pick_many(cands, self, shortfall)
            if len(victims) < shortfall:
                raise MemoryError("no evictable page")   # unreachable under
        if victims or missing:                           # scheduler budgets
            self.pool.exchange(victims, missing)
            self._note_swap_in(len(missing))
        for r in reqs:
            idx = r.pos // P
            if idx >= len(self.pool.request_pages(r.request_id)):
                self.pool.allocate(r.request_id, idx, self.step_count)

    def _ensure_free_hbm(self, n: int, needed: List[int]):
        shortfall = n - len(self.pool.free_hbm)
        if shortfall <= 0:
            return
        exclude = set(needed)
        cands = [p for p in self.pool.pages.values()
                 if p.hbm_slot is not None and p.page_id not in exclude]
        victims = self.eviction.pick_many(cands, self, shortfall)
        if len(victims) < shortfall:
            raise MemoryError("no evictable page")   # unreachable under
        self.pool.swap_out_many(victims)             # scheduler budgets

    # ============================================================ stepping
    def _decode_one(self, req: Request, token: int) -> int:
        """Single-request decode (the chunked-prefill oracle path)."""
        self._prepare_batch([req])
        self.prefill_dispatches += 1
        return self._run_batch([(req, token)])[0]

    def _schedule(self) -> List[Request]:
        """Pack active requests (policy decode order) under the HBM-slot
        and logical-page budgets, so the batch can always be made resident
        without evicting its own members and every allocation can succeed."""
        active = [r for r in self.requests.values() if r.state == "active"]
        if not active:
            return []
        budget = self.scheduler.step_budget(self)
        row_cap = min(self.cfg.max_batch, max(budget.decode_requests, 0))
        P = self.cfg.page_size
        sched: List[Request] = []
        hbm_budget = self.usable_hbm_pages
        logical_budget = self.free_logical_pages()
        for r in self.scheduler.decode_order(active, self):
            if len(sched) == row_cap:
                break
            n_pages = len(self.pool.request_pages(r.request_id))
            need = max(n_pages, r.pos // P + 1)
            if need > self.usable_hbm_pages:
                # Outgrew the fast tier entirely: can never decode again.
                self._finish(r, reason="truncated")
                continue
            grow = need - n_pages
            if need > hbm_budget or grow > logical_budget:
                self.starved_steps += 1     # waits, aging via last_scheduled
                continue
            sched.append(r)
            hbm_budget -= need
            logical_budget -= grow
        return sched

    def step(self) -> Dict[int, int]:
        """One engine step: admit, advance interleaved prefills, schedule,
        decode, bookkeeping."""
        self.step_count += 1
        self.scheduler.on_step(self)
        self._admit_waiting()
        self._advance_prefills()
        sched = self._schedule()
        if not sched and any(r.state == "active"
                             for r in self.requests.values()):
            self._reclaim_logical_pages()
            sched = self._schedule()
        out: Dict[int, int] = {}
        if sched:
            pairs = []
            for r in sched:
                nxt = (r.generated[-1] if r.generated
                       else (r.tokens[-1] if r.tokens else 1))
                pairs.append((r, nxt))
            self._prepare_batch(sched)
            toks = self._run_batch(pairs)
            for r, t in zip(sched, toks):
                r.generated.append(int(t))
                self.scheduler.on_tokens(r, 1, self)
                out[r.request_id] = int(t)
                if int(t) in r.params.stop_token_ids:
                    self._finish(r, reason="stop")
                elif len(r.generated) >= r.max_new:
                    self._finish(r, reason="length")
        self._tick_controllers()
        return out

    def _tick_controllers(self) -> None:
        """MaybeMigrate for every guidance controller, in a FIXED order:
        KV pages -> shared prefixes -> expert weights.  The order is part
        of the engine's replay contract — each controller's event stream
        is pinned by regression tests, and a reorder would change which
        controller sees the interval's free HBM first.  Add new
        controllers at the END of this list."""
        for rt in (self.runtime, self.prefix_runtime, self.expert_runtime):
            if rt is not None:
                rt.on_step()

    def _finish(self, req: Request, reason: str = "length"):
        """Lifecycle cleanup: free pages, prune the live tables (requests,
        eviction recs, logits), park the result in ``finished`` with its
        ``finish_reason`` (stop | length | truncated | cancelled)."""
        assert reason in ("stop", "length", "truncated", "cancelled"), reason
        self._pending_chains.pop(req.request_id, None)
        self._release_pages(req.request_id)
        req.state = "finished"
        req.finish_reason = reason
        req.truncated = reason == "truncated"
        if req.truncated:
            self.truncations += 1
        self.finish_counts[reason] += 1
        self.requests.pop(req.request_id, None)
        self.last_logits.pop(req.request_id, None)
        self.finished[req.request_id] = req

    def _run_batch(self, pairs) -> List[int]:
        """Decode one batch.  Pages are already resident and write pages
        allocated (``_prepare_batch``).  The next token comes back sampled
        from inside the jitted dispatch: each row's ``SamplingParams`` ride
        along as batched arrays, and the PRNG folds the row's absolute
        stream position (== ``lengths``), so a preempted-and-recomputed
        request resamples the identical stream."""
        B = self.cfg.max_batch
        MP = self.cfg.max_pages_per_seq
        tokens = np.zeros((B, 1), np.int32)
        table = np.full((B, MP), -1, np.int32)
        lengths = np.zeros((B,), np.int32)
        wslot = np.full((B,), self.scratch_slot, np.int32)
        woff = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        seeds = np.zeros((B,), np.int32)
        temperature = np.zeros((B,), np.float32)   # 0 = greedy argmax
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        for i, (req, tok) in enumerate(pairs):
            req.last_scheduled = self.step_count
            slot, off = self._page_for_write(req)
            req.pos += 1
            pages = self.pool.request_pages(req.request_id)
            for p in pages:
                p.accesses += 1          # exact access model
                table[i, p.index_in_seq] = p.hbm_slot
            tokens[i, 0] = tok
            lengths[i] = req.pos
            wslot[i] = slot
            woff[i] = off
            active[i] = True
            sp = req.params
            # seed=None means "independent stream per request": derive from
            # the request id so identical prompts in one batch do not
            # sample bitwise-identical tokens, while replay (same request
            # id, same positions) stays exact.  Auto-derived seeds live in
            # the int32 SIGN-BIT half of the space — explicit seeds are
            # validated to [0, 2**31), so a user seed can never alias a
            # request-id-derived stream.
            if sp.seed is not None:
                seeds[i] = sp.seed
            else:
                seeds[i] = (0x80000000 | (req.request_id & 0x7FFFFFFF)) \
                    - (1 << 32)
            temperature[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
        # Greedy-only batches (the default) take the argmax-epilogue
        # variant: no sort/cumsum/Gumbel work on the hot path, and the
        # sampled variant is never even compiled unless someone samples.
        greedy = all(req.params.greedy for req, _ in pairs)
        args = (jnp.asarray(tokens), jnp.asarray(table),
                jnp.asarray(lengths), jnp.asarray(wslot), jnp.asarray(woff),
                jnp.asarray(active), jnp.asarray(seeds),
                jnp.asarray(temperature), jnp.asarray(top_k),
                jnp.asarray(top_p))
        if self.expert_store is not None:
            logits, toks, nk, nv = self._tiered_decode(
                *args, use_sampler=not greedy)
        else:
            decode = self._decode_greedy if greedy else self._decode_sampled
            logits, toks, nk, nv = decode(
                self.params, self.pool.k_hbm, self.pool.v_hbm, *args)
        self.pool.k_hbm, self.pool.v_hbm = nk, nv
        if self.cfg.keep_logits:
            logits_np = np.asarray(logits)
            for i, (req, _) in enumerate(pairs):
                self.last_logits[req.request_id] = logits_np[i]
        toks = np.asarray(toks)
        return [int(toks[i]) for i in range(len(pairs))]

    # --------------------------------------------------------- telemetry
    def stats(self) -> Dict[str, float]:
        pc = self.prefix_cache
        prefix = {
            "prefix_lookups": pc.lookups,
            "prefix_hit_requests": pc.hit_requests,
            "prefix_hit_pages": pc.hit_pages,
            "prefix_hit_rate": pc.hit_rate,
            "prefix_cached_pages": len(pc),
            "prefix_inserted_pages": pc.inserted_pages,
            "prefix_evicted_pages": pc.evicted_pages,
        } if pc is not None else {}
        es = self.expert_store
        expert = {
            "expert_cache_slots": es.cache_slots,
            "expert_resident_blocks": sum(
                1 for b in es.blocks.values() if b.slot is not None),
            "expert_demand_fetches": es.demand_fetches,
            "expert_prefetch_fetches": es.prefetch_fetches,
            "expert_prefetch_hits": es.prefetch_hits,
            "expert_dropped_prefetches": es.dropped_prefetches,
            "expert_evictions": es.evictions,
            "expert_bytes_fetched": es.bytes_fetched,
            "expert_transfer_events": es.transfer_events,
        } if es is not None else {}
        return {
            **prefix,
            **expert,
            "saved_prefill_tokens": self.saved_prefill_tokens,
            "steps": self.step_count,
            "swap_ins": self.pool.swaps_in,
            "swap_outs": self.pool.swaps_out,
            "bytes_moved": self.pool.bytes_moved,
            "transfer_events": self.pool.transfer_events,
            "exported_pages": self.pool.exported_pages,
            "imported_pages": self.pool.imported_pages,
            "hbm_pages_used": self.pool.hbm_used(),
            "live_requests": len(self.requests),
            "waiting_requests": len(self.wait_queue),
            # Queue depth counts LIVE waiting requests (stale queue entries
            # from cancel/migrate excluded), plus mid-prefill occupancy.
            "queue_depth": sum(1 for r in self.requests.values()
                               if r.state == "waiting"),
            "prefilling_requests": sum(1 for r in self.requests.values()
                                       if r.state == "prefilling"),
            "finished_requests": len(self.finished),
            "prefill_dispatches": self.prefill_dispatches,
            "prefill_tokens": self.prefill_tokens,
            "prefill_chunks": self.prefill_chunks,
            "admissions": self.admissions,
            "admission_wait_steps": self.admission_wait_steps,
            "mean_admission_wait_steps": (
                self.admission_wait_steps / max(self.admissions, 1)),
            "preemptions": self.preemptions,
            "starved_steps": self.starved_steps,
            "truncations": self.truncations,
            "finished_stop": self.finish_counts["stop"],
            "finished_length": self.finish_counts["length"],
            "finished_truncated": self.finish_counts["truncated"],
            "finished_cancelled": self.finish_counts["cancelled"],
        }
