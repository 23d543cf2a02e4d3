"""Continuous-batching serving engine with guided KV-page tiering.

The engine serves dense/MoE decoder models from a paged two-tier KV cache
(serve/kvcache.py); a model whose layers mix Mamba-2 and attention
(``ModelConfig.layer_types``) keeps K/V pages for its attention layers and
a recurrent-state slot per request for its Mamba layers (DESIGN.md §17).
Each *request* is an allocation site; its pages are the chunks.  The
request lifecycle is explicit:

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
  wait-queue head needs logical pages.  Latent attention is the exception:
  it prefills in the plain form and decodes absorbed, equal to rounding.
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
from ..core.spans import span
from ..dist.sharding import active_mesh
from ..models.layers import (
    lm_head,
    mla_absorb,
    mla_project,
    mla_unabsorbed,
    mla_values,
    mlp,
    rmsnorm,
    rope,
)
from ..models.moe import apply_dropless_flat, moe_decode, route_tokens
from ..models.ssm import (
    conv_window,
    gated_out,
    split_xbc,
    ssm_forward,
    ssm_project,
)
from ..models.transformer import Model
from .eviction import make_eviction_policy
from .expert_store import ExpertBackend, ExpertCacheMissError, ExpertStore
from .kvcache import PageExport, PagedKVPool, StatePool
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
    # HBM cache capacity in expert blocks, shared across the MoE layers
    # (a model's leading dense layers have no blocks); 0 means every
    # (MoE layer, expert) block fits (moe_layers * n_experts slots).  Must
    # hold at least one dispatch's working set:
    # min(n_experts, max_batch * top_k).
    expert_cache_size: int = 0


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
        if model.cfg.mixed:
            # Mamba layers keep a recurrent state per request, which the
            # tiered expert pipeline does not carry and a shared prefix's
            # pages do not capture.
            if cfg.expert_offchip:
                raise ValueError(
                    "ServeConfig.expert_offchip does not serve a model with "
                    "Mamba layers yet: their experts stay resident")
            if cfg.enable_prefix_cache:
                raise ValueError(
                    "ServeConfig.enable_prefix_cache needs state snapshots "
                    "for a model with Mamba layers: a cached prefix's pages "
                    "hold no recurrent state")
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
            size = cfg.expert_cache_size or model.cfg.moe_layers * E
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
        # The most tokens one prefill dispatch takes: a MoE dispatch's
        # float32 combine of its routed expert outputs (tokens x top_k x
        # d_model values) stays within 2**27 values (512 MiB).
        routed = model.cfg.top_k * model.cfg.d_model
        self._dispatch_cap = (1 << (((1 << 27) // routed).bit_length() - 1)
                              if model.cfg.family == "moe" else 1 << 62)
        self.device = device if device is not None else jax.devices()[0]
        mc = model.cfg
        # Latent attention caches one latent per token and layer, read as
        # keys and values both: a one-array pool of latent rows.
        kv_heads, head_dim, n_arrays = (
            (0, model.mla_cfg.latent_dim, 1) if mc.mla
            else (mc.kv_heads, model.attn_cfg.head_dim, 2))
        kv_layers = mc.n_layers
        self.states: Optional[StatePool] = None
        if mc.mixed:
            # Pages hold the attention layers' K/V alone, each token's
            # heads as one flat row (the layout the kernel reads); every
            # Mamba layer keeps a state per request instead, one slot for
            # each row of a batch.
            kv_heads, head_dim = 0, mc.kv_heads * model.attn_cfg.head_dim
            kv_layers = mc.mixers.count("attention")
            sc = model.ssm_cfg
            self.states = StatePool(
                mc.mixers.count("mamba"), cfg.max_batch,
                conv=(sc.conv_width - 1) * sc.conv_dim,
                state=(sc.state_dim, sc.d_inner), dtype=mc.dtype,
                device=self.device)
        self.pool = PagedKVPool(
            n_layers=kv_layers, page_size=cfg.page_size,
            kv_heads=kv_heads, head_dim=head_dim,
            hbm_pages=cfg.hbm_pages, host_pages=cfg.host_pages,
            dtype=mc.dtype, device=self.device, n_arrays=n_arrays)
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
        # expert weights are off-chip (its demand management works under
        # any policy); the third GuidanceRuntime rides only under gdt,
        # like the KV and prefix controllers.
        self.expert_store: Optional[ExpertStore] = None
        self.expert_backend: Optional[ExpertBackend] = None
        self.expert_runtime: Optional[GuidanceRuntime] = None
        if cfg.expert_offchip:
            # The store holds the routed experts of the MoE layers alone:
            # its layer l is the model's layer first_dense_layers + l.
            E = self.model.moe_cfg.padded_experts
            moe_params = params["layers"]["moe"]
            self.expert_store = ExpertStore(
                moe_params, mc.moe_layers, E,
                cfg.expert_cache_size or mc.moe_layers * E,
                device=self.device)
            # The routed expert stacks live in the store's tiers now; serve
            # against a param view holding only the router (shared experts
            # stay beside it, resident).  ``params``
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
            def clock():
                return self.step_count

            def guidance(fast_capacity_bytes):
                return GuidanceConfig(
                    strategy=cfg.strategy,
                    fast_capacity_bytes=fast_capacity_bytes,
                    interval_steps=cfg.interval_steps,
                    decay=cfg.access_decay,
                    num_fragments=cfg.num_fragments,
                    skip_empty_intervals=True)

            # The reserved scratch slot is not placeable capacity.
            kv_bytes = (cfg.hbm_pages - 1) * self.pool.page_bytes
            self.kv_backend = PagedKVBackend(self.pool, self.requests, clock)
            self.runtime = GuidanceRuntime(
                self.kv_backend, hw, guidance(kv_bytes), clock=clock)
            if self.prefix_cache is not None:
                self.prefix_backend = PrefixBackend(self.prefix_cache, clock)
                self.prefix_runtime = GuidanceRuntime(
                    self.prefix_backend, hw, guidance(kv_bytes), clock=clock)
            if self.expert_store is not None:
                self.expert_backend = ExpertBackend(self.expert_store, clock)
                self.expert_runtime = GuidanceRuntime(
                    self.expert_backend, hw,
                    guidance(self.expert_store.cache_bytes), clock=clock)
        # Every program that returns the pools updated takes them donated
        # (argument 1), so XLA writes them in place instead of copying them
        # whole; ``self.pool.hbm`` is re-bound to what it returns.
        self._decode_greedy = _jit(
            functools.partial(self._decode_scan, False), donate_argnums=1)
        self._decode_sampled = _jit(
            functools.partial(self._decode_scan, True), donate_argnums=1)
        self._prefill = _jit(self._prefill_scan, donate_argnums=1)
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
        self.host_syncs = 0            # blocking device->host reads (_read)
        # Requests whose recurrent state a preemption dropped, and how
        # many were admitted again (their state rebuilt by re-prefill).
        self._dropped_state: set = set()
        self.ssm_recomputes = 0
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
    def _layer_body(self, lp, x, pools, *, positions, write_slot,
                    write_off, row_mask, lane_mask, rows, unrows, attend):
        """ONE transformer layer body shared by the jitted decode and
        one-shot prefill closures — a single definition is what keeps
        one-shot prefill bitwise-equal to decode (the invariant
        preemption-by-recompute losslessness rests on).

        The two paths differ only in where the row axis lives (decode:
        batch of B single-token rows, x (B,1,d); prefill: one sequence of
        S token rows, x (1,S,d)) and in the attention call.  ``rows``
        flattens a (.., ., H, dh) projection to (R, H, dh), ``unrows``
        lifts an (R, d) result back to x's layout, ``attend``
        (``_decode_attend`` / ``_prefill_attend``) returns (R, H, dv).
        ``pools`` is the layer's slice of every pool array.  Masked rows
        scatter zeros to the reserved scratch slot and carry zero residuals
        — deterministic, never garbage.
        """
        x, h2, pools = self._attn_half(
            lp, x, pools, positions=positions, write_slot=write_slot,
            write_off=write_off, row_mask=row_mask, lane_mask=lane_mask,
            rows=rows, unrows=unrows, attend=attend)
        x = self._ffn_half(lp, x, h2, lane_mask)
        return x, pools

    def _attn_half(self, lp, x, pools, *, positions, write_slot, write_off,
                   row_mask, lane_mask, rows, unrows, attend):
        """Attention through the pre-FFN rmsnorm.  Split from
        ``_layer_body`` so the tiered expert path (expert_offchip) can run
        the identical ops up to the router, sync the routing picks to the
        host, and resume with ``_ffn_half``'s math against cache slots —
        the split point changes WHERE the jit boundary falls, never which
        ops run (on the CPU the outputs are bitwise-equal; on the TPU the
        two programs round differently).

        Latent attention writes each token's latent into its one pool and
        ``attend(q_nope, q_pe, pool, p)`` reads it."""
        if self.model.cfg.mla:
            return self._mla_attn_half(
                lp, x, pools, positions=positions, write_slot=write_slot,
                write_off=write_off, row_mask=row_mask, lane_mask=lane_mask,
                rows=rows, unrows=unrows, attend=attend)
        acfg = self.model.attn_cfg
        eps = self.model.cfg.norm_eps
        kp, vp = pools
        h = rmsnorm(lp["ln1"], x, eps)
        q = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wq"])
        k1 = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"])
        v1 = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"])
        if acfg.use_rope:
            q = rope(q, positions, acfg.rope_theta)
            k1 = rope(k1, positions, acfg.rope_theta)
        # A flat pool (N, P, K*dh) takes each token's heads as one row.
        kr, vr = rows(k1), rows(v1)
        if kp.ndim == 3:
            kr, vr = kr.reshape(kr.shape[0], -1), vr.reshape(vr.shape[0], -1)
        m = row_mask[(slice(None),) + (None,) * (kp.ndim - 2)]
        kp = kp.at[write_slot, write_off].set(
            jnp.where(m, kr, 0).astype(kp.dtype))
        vp = vp.at[write_slot, write_off].set(
            jnp.where(m, vr, 0).astype(vp.dtype))
        o = attend(rows(q), kp, vp)                      # (R, H, dh)
        y = jnp.einsum("rhk,hkd->rd",
                       o.reshape(o.shape[0], acfg.n_heads, acfg.head_dim),
                       lp["attn"]["wo"])
        x = x + self._residual(jnp.where(lane_mask, unrows(y), 0))
        h2 = rmsnorm(lp["ln2"], x, eps)
        return x, h2, (kp, vp)

    def _mla_attn_half(self, lp, x, pools, *, positions, write_slot,
                       write_off, row_mask, lane_mask, rows, unrows, attend):
        p = lp["attn"]
        (lat_pool,) = pools
        h = rmsnorm(lp["ln1"], x)
        q_nope, q_pe, lat = mla_project(p, h, positions, self.model.mla_cfg)
        lat_pool = lat_pool.at[write_slot, write_off].set(
            jnp.where(row_mask[:, None], rows(lat), 0).astype(lat_pool.dtype))
        o = attend(rows(q_nope), rows(q_pe), lat_pool, p)   # (R, H, dv)
        y = jnp.einsum("rhv,hvd->rd", o, p["wo"])
        x = x + jnp.where(lane_mask, unrows(y), 0)
        h2 = rmsnorm(lp["ln2"], x)
        return x, h2, (lat_pool,)

    def _decode_attend(self, page_table, lengths):
        """``attend`` of a decode dispatch: GQA over the K/V pools, or
        latent attention absorbed (q_nope W_uk^T scores the cached latents
        beside q_pe, the weighted latents go through W_uv)."""
        from ..kernels.ops import paged_attention, paged_mla_decode

        if not self.model.cfg.mla:
            acfg = self.model.attn_cfg
            return lambda q, kp, vp: paged_attention(
                q, kp, vp, page_table, lengths, window=acfg.window,
                scale=acfg.scale)
        mcfg = self.model.mla_cfg

        def attend(q_nope, q_pe, pool, p):
            o_lat = paged_mla_decode(
                mla_absorb(p, q_nope, q_pe), pool, page_table, lengths,
                scale=mcfg.softmax_scale(), dv=mcfg.kv_lora_rank)
            return mla_values(p, o_lat)
        return attend

    def _prefill_attend(self, page_table, lengths, start):
        """``attend`` of a prefill dispatch of S rows at absolute positions
        ``start ..``: GQA over the K/V pools, or latent attention in the
        plain form — the request's cached latents (the table's pages, this
        dispatch's rows written) expanded to per-head keys and values, and
        the flash-attention kernel over them with the S query rows at their
        absolute positions of a zero query block as long as the context,
        so causality alone keeps every row to its own prefix."""
        from ..kernels.ops import flash_attention, paged_prefill

        if not self.model.cfg.mla:
            acfg = self.model.attn_cfg
            return lambda q, kp, vp: paged_prefill(
                q, kp, vp, page_table, lengths, window=acfg.window,
                scale=acfg.scale)
        mcfg = self.model.mla_cfg

        def attend(q_nope, q_pe, pool, p):
            S = q_nope.shape[0]
            ctx = pool[jnp.maximum(page_table, 0)]
            # S zero rows past the table's end: a bucket-padded row never
            # reaches past the context, whatever ``start`` is.
            ctx = jnp.pad(ctx.reshape(-1, ctx.shape[-1]), ((0, S), (0, 0)))
            q, k, v = mla_unabsorbed(p, q_nope, q_pe, ctx, mcfg)
            qf = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros(k.shape, q.dtype), q, start, 0)
            dv = v.shape[-1]
            vf = jnp.pad(v, ((0, 0), (0, 0), (0, k.shape[-1] - dv)))
            o = flash_attention(qf[None], k[None], vf[None], causal=True,
                                scale=mcfg.softmax_scale())[0]
            return jax.lax.dynamic_slice_in_dim(o, start, S)[..., :dv]
        return attend

    def _residual(self, y):
        """A sublayer's output as the residual stream takes it: scaled by
        the model's residual multiplier (Granite's), where it has one."""
        rm = self.model.cfg.residual_multiplier
        return y if rm == 1.0 else (y * rm).astype(y.dtype)

    def _ffn_half(self, lp, x, h2, lane_mask, layer=None, experts=None):
        """FFN + residual, the second half of ``_layer_body``: the routed
        experts (plus the shared ones), or a dense MLP.  ``experts``: the
        MoE weights of every layer, stacked, and ``layer`` this layer's
        index into them — the grouped GEMM then reads this layer's
        experts in place through its slot map (a slice of the stack would
        be copied out for the kernel)."""
        if experts is not None:
            mcfg = self.model.moe_cfg
            B, S, d = h2.shape
            gates, picks = route_tokens(lp["moe"]["router"],
                                        h2.reshape(B * S, d), mcfg)

            def flat(w):
                return w.reshape((-1,) + w.shape[2:])

            d_out = apply_dropless_flat(
                gates, picks, h2, flat(experts["w_gate"]),
                flat(experts["w_up"]), flat(experts["w_down"]), mcfg,
                expert_slots=layer * mcfg.held + jnp.arange(
                    mcfg.padded_experts, dtype=jnp.int32))
            if "shared" in lp:
                d_out = d_out + mlp(lp["shared"], h2)
            return x + self._residual(jnp.where(lane_mask, d_out, 0))
        if "moe" in lp:
            # Same dropless routing + grouped GEMM as model.prefill, so a
            # token's expert assignment never depends on how the stream is
            # chunked or batched.
            d = moe_decode(lp["moe"], h2, self.model.moe_cfg)
            if "shared" in lp:
                d = d + mlp(lp["shared"], h2)
        else:
            d = mlp(lp["mlp"], h2)
        return x + self._residual(jnp.where(lane_mask, d, 0))

    # ===================================================== dispatch parts
    # Each part of a dispatch is defined once.  The resident programs run
    # them inside one jitted scan over the layers; the tiered pipeline jits
    # the same definitions as its pieces.
    def _decode_frame(self, page_table, lengths, write_slot, write_off,
                      active):
        """The layer keywords of a decode dispatch: a batch of B
        single-token rows, x (B,1,d).  page_table: (B,MP) HBM slots or -1;
        lengths: (B,) incl. the new token; write_slot/off: (B,) where the
        new token's KV goes; active: (B,) bool — inactive rows are masked
        to deterministic zeros rather than carrying garbage."""
        return dict(positions=(lengths - 1)[:, None],
                    write_slot=write_slot, write_off=write_off,
                    row_mask=active, lane_mask=active[:, None, None],
                    rows=lambda t: t[:, 0], unrows=lambda y: y[:, None],
                    attend=self._decode_attend(page_table, lengths))

    def _prefill_frame(self, page_table, slots, offs, positions, lengths,
                       valid):
        """The layer keywords of a prefill dispatch: one sequence of S
        rows, x (1,S,d), laid out by ``_prefill_prologue``.  page_table:
        (MP,) the request's pages, cached prefix included; slots/offs: (S,)
        each row's write target (the scratch slot for padding).  Rows
        attend by ABSOLUTE length over the table, so a suffix-only dispatch
        replays the whole-prompt computation bitwise."""
        return dict(positions=positions[None],
                    write_slot=slots, write_off=offs,
                    row_mask=valid, lane_mask=valid[None, :, None],
                    rows=lambda t: t[0], unrows=lambda y: y[None],
                    attend=self._prefill_attend(page_table, lengths,
                                                positions[0]))

    def _embed(self, params, tokens):
        return self.model.embed_tokens(params, tokens)

    def _prefill_prologue(self, params, tokens, n_real, start):
        """tokens: (S,) the padded suffix; n_real: () int32 live rows;
        start: () int32 absolute position of row 0 (0 for an uncached
        prompt, the covered token count after a prefix-cache hit — traced,
        so hits never recompile).  Returns the embedded rows and each
        row's position, causal length (0 for padding) and liveness."""
        S = tokens.shape[0]
        local = jnp.arange(S, dtype=jnp.int32)
        positions = start + local
        valid = local < n_real
        lengths = jnp.where(valid, positions + 1, 0)
        return self._embed(params, tokens[None]), positions, lengths, valid

    def _tail(self, with_sampler: bool):
        """Final norm, head and next token of a decode dispatch.  The
        greedy variant's epilogue is a plain ``argmax`` (zero sampling
        overhead on the default path); the sampled variant runs the full
        in-dispatch sampler.  They agree bitwise on greedy rows (the
        sampler short-circuits ``temperature<=0`` to the same argmax), so
        the scheduler picks per batch and each compiles only on first use
        — a pure-greedy workload never compiles the sampled path."""
        from ..kernels.ops import sample_tokens

        def tail(params, x, lengths, active, seeds, temperature, top_k,
                 top_p):
            """seeds/temperature/top_k/top_p: (B,) per-request sampling
            knobs; the sampler folds the next token's stream position
            ``lengths`` into the PRNG — the replay contract."""
            mc = self.model.cfg
            x = rmsnorm(params["final_ln"], x, mc.norm_eps)
            if mc.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", x,
                                    params["tied_embed"]["tok"],
                                    preferred_element_type=F32)[:, 0]
            else:
                logits = lm_head(params["head"], x)[:, 0]
            if mc.logits_scaling != 1.0:
                logits = logits / mc.logits_scaling
            logits = jnp.where(active[:, None], logits, 0.0)
            if with_sampler:
                next_tokens = sample_tokens(logits, seeds, lengths,
                                            temperature, top_k, top_p)
            else:
                next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits, next_tokens
        return tail

    def _scan_layers(self, params, pools, x, frame, *frame_args,
                     mamba=None):
        """Every layer in one scan; ``frame(*frame_args)`` builds the layer
        keywords inside the scan body.  A Mamba-2/attention stack runs
        ``_mixed_layers`` instead, its Mamba layers through ``mamba``."""
        if mamba is not None:
            return self._mixed_layers(params, pools, x, frame(*frame_args),
                                      mamba)

        def body(carry, xs):
            lp, *pl = xs             # pl: each pool's (N,P,K,dh)
            return self._layer_body(lp, carry, tuple(pl),
                                    **frame(*frame_args))
        return self.model.scan_stacks(body, x, params, pools)

    def _mixed_layers(self, params, pools, x, frame, mamba):
        """Every layer of a Mamba-2/attention stack, unrolled in depth
        order (the two kinds' weights are separate stacks), each mixer
        followed by its FFN half.  ``pools`` is the K/V pools of the
        attention layers, then the recurrent state pool's (conv, ssm);
        ``mamba(lp, mp, x, states, j)`` is the mixer half of Mamba layer
        ``j`` (``_mamba_decode_half``, ``_mamba_prefill_half``)."""
        kv, states = pools[:-2], pools[-2:]
        lay = params["layers"]
        at = {"attention": 0, "mamba": 0}
        for l, kind in enumerate(self.model.cfg.mixers):
            j = at[kind]
            at[kind] += 1
            lp = {k: jax.tree.map(lambda a: a[l], lay[k])
                  for k in ("ln1", "ln2", "shared") if k in lay}
            lp["moe"] = {"router": lay["moe"]["router"][l]}
            if kind == "attention":
                lp["attn"] = jax.tree.map(lambda a: a[j], lay["attn"])
                x, h2, pl = self._attn_half(lp, x, tuple(p[j] for p in kv),
                                            **frame)
                kv = tuple(p.at[j].set(q) for p, q in zip(kv, pl))
            else:
                x, h2, states = mamba(
                    lp, jax.tree.map(lambda a: a[j], lay["mamba"]), x,
                    states, j)
            x = self._ffn_half(lp, x, h2, frame["lane_mask"], layer=l,
                               experts=lay["moe"])
        return x, kv + states

    def _mamba_decode_half(self, slots, lane_mask):
        """The mixer half of a Mamba-2 layer over a decode batch, x
        (B,1,d): each row's conv and SSM state at ``slots`` (B,) — an
        inactive row's at the scratch slot — read, advanced one token and
        written back in place (``ops.ssm_decode``)."""
        from ..kernels.ops import ssm_decode

        sc, eps = self.model.ssm_cfg, self.model.cfg.norm_eps

        def half(lp, mp, x, states, j):
            conv, ssm = states
            z, xbc, dt = ssm_project(mp, rmsnorm(lp["ln1"], x, eps)[:, 0],
                                     sc)
            B = xbc.shape[0]
            window = jnp.concatenate(
                [conv[j, slots].reshape(B, -1, sc.conv_dim),
                 xbc[:, None].astype(conv.dtype)], axis=1)
            conv = conv.at[j, slots].set(window[:, 1:].reshape(B, -1))
            xs, Bm, Cm = split_xbc(conv_window(mp, window, x.dtype), sc)
            y, ssm = ssm_decode(ssm, jnp.int32(j), slots,
                                xs.reshape(xs.shape[0], -1), dt,
                                -jnp.exp(mp["a_log"]), mp["D"], Bm, Cm)
            out = gated_out(mp, y, z, sc, x.dtype)[:, None]
            x = x + self._residual(jnp.where(lane_mask, out, 0))
            return x, rmsnorm(lp["ln2"], x, eps), (conv, ssm)
        return half

    def _mamba_prefill_half(self, slot, n_real, lane_mask):
        """The mixer half of a Mamba-2 layer over one sequence's S rows, x
        (1,S,d): the chunked scan from the request's states at ``slot``,
        which it leaves as the last of the ``n_real`` live rows left them
        (padding rows leave them untouched)."""
        from ..kernels.ops import ssm_state_read, ssm_state_write

        sc, eps = self.model.ssm_cfg, self.model.cfg.norm_eps

        def half(lp, mp, x, states, j):
            conv, ssm = states
            N, HP = ssm.shape[2:]
            out, nc, ns = ssm_forward(
                mp, rmsnorm(lp["ln1"], x, eps), sc,
                conv_state=conv[j, slot].reshape(1, -1, sc.conv_dim),
                ssm_state=ssm_state_read(ssm, jnp.int32(j), slot).reshape(
                    1, N, sc.n_heads, sc.head_dim),
                n_real=n_real)
            conv = conv.at[j, slot].set(nc.reshape(-1).astype(conv.dtype))
            ssm = ssm_state_write(ssm, jnp.int32(j), slot,
                                  ns.reshape(N, HP))
            x = x + self._residual(jnp.where(lane_mask, out, 0))
            return x, rmsnorm(lp["ln2"], x, eps), (conv, ssm)
        return half

    # ================================================== resident programs
    def _decode_scan(self, with_sampler, params, pools, tokens, page_table,
                     lengths, write_slot, write_off, active, seeds,
                     temperature, top_k, top_p, state_slots=None):
        """One decode step of the batch in one scan.  tokens: (B,1); the
        rest as ``_decode_frame`` and ``_tail`` take them; a model with
        Mamba layers also takes each row's recurrent-state slot."""
        x, pools = self._scan_layers(
            params, pools, self._embed(params, tokens), self._decode_frame,
            page_table, lengths, write_slot, write_off, active,
            mamba=None if state_slots is None else self._mamba_decode_half(
                state_slots, active[:, None, None]))
        return (*self._tail(with_sampler)(
            params, x, lengths, active, seeds, temperature, top_k, top_p),
            pools)

    def _prefill_scan(self, params, pools, tokens, page_table, slots, offs,
                      n_real, start, state_slot=None):
        """One-shot prompt ingestion: a single jitted call writes S tokens'
        K/V into their page-table slots and attends with per-token causal
        lengths via ``ops.paged_prefill`` — the same layer body decode
        runs, so the result is bitwise-equal to chunked ingestion.  Latent
        attention prefills in the plain form and decodes absorbed: equal
        in exact arithmetic, not bitwise (``_prefill_attend``).  A model
        with Mamba layers continues the request's recurrent state at
        ``state_slot``."""
        x, positions, lengths, valid = self._prefill_prologue(
            params, tokens, n_real, start)
        return self._scan_layers(
            params, pools, x, self._prefill_frame, page_table, slots, offs,
            positions, lengths, valid,
            mamba=None if state_slot is None else self._mamba_prefill_half(
                state_slot, n_real, valid[None, :, None]))[1]

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
        Only the jit boundaries move, which the bitwise parity tests pin on
        the CPU (on the TPU the two programs round differently, so there
        the tiered result is pinned across cache sizes instead).  A
        leading dense layer is one jitted piece (attention and its MLP)
        with nothing to read back; shared experts are added to the routed
        output inside the FFN piece.  The attention and dense pieces take
        the dispatch kind (``"decode"`` or ``"prefill"``) as the static
        ``dispatch`` keyword, which picks the frame of their arguments.

        Ownership of the pools: every piece that writes a layer's K/V (or
        latent) rows takes the whole pool tuple donated (argument 1) and
        returns it, so XLA updates the one layer's slice in place instead
        of copying every pool array whole on each call.  The donated
        arrays are dead once the call is dispatched, so the caller re-binds
        ``self.pool.hbm`` to the returned tuple after EVERY piece, not once
        per step: an error between pieces (``ExpertCacheMissError`` in
        ``_tiered_ffn``) then leaves the pool naming live arrays.  Nothing
        else may hold a pool array across a piece.
        """
        mcfg = self.model.moe_cfg
        nd = self.model.cfg.first_dense_layers
        frames = {"decode": self._decode_frame,
                  "prefill": self._prefill_frame}

        def slice_layer(tree, l):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, l, 0, keepdims=False), tree)

        def put_layer(pools, pl, l):
            return tuple(jax.lax.dynamic_update_index_in_dim(a, b, l, 0)
                         for a, b in zip(pools, pl))

        # ``l`` is the model's layer (the pools' index); a MoE layer's
        # weights sit at l - first_dense_layers of ``params["layers"]``.
        def attn(params, pools, x, l, *frame_args, dispatch):
            frame = frames[dispatch](*frame_args)
            lp = slice_layer(params["layers"], l - nd if nd else l)
            pl = slice_layer(pools, l)
            x, h2, pl = self._attn_half(lp, x, pl, **frame)
            B, S, d = h2.shape
            gates, experts = route_tokens(
                lp["moe"]["router"], h2.reshape(B * S, d), mcfg)
            return x, h2, gates, experts, put_layer(pools, pl, l)

        def dense(params, pools, x, l, *frame_args, dispatch):
            # A leading dense layer: attention and its MLP in one piece,
            # no expert in it and nothing for the host to read.
            frame = frames[dispatch](*frame_args)
            lp = slice_layer(params["dense_layers"], l)
            x, pl = self._layer_body(lp, x, slice_layer(pools, l), **frame)
            return x, put_layer(pools, pl, l)

        def ffn(x, h2, wg, wu, wd, slot_map, gates, experts, lane_mask,
                shared=None, l=None):
            """``shared``: the shared experts' stacked weights, layer ``l``
            of them added to the routed output."""
            d = apply_dropless_flat(gates, experts, h2, wg, wu, wd, mcfg,
                                    expert_slots=slot_map)
            if shared is not None:
                d = d + mlp(slice_layer(shared, l), h2)
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

        piece = functools.partial(_jit, donate_argnums=1,
                                  static_argnames="dispatch")
        self._t_embed = _jit(self._embed)
        self._t_prefill_prologue = _jit(self._prefill_prologue)
        self._t_attn = piece(attn)
        self._t_dense = piece(dense)
        self._t_ffn = _jit(ffn)
        self._t_spec_route = _jit(spec_route)
        self._t_tail_greedy = _jit(self._tail(False))
        self._t_tail_sampled = _jit(self._tail(True))

    def _tiered_ffn(self, l, x, h2, gates, experts, lane_mask, token_mask):
        """Host half of one tiered layer: sync the routed picks, make them
        resident (commit the in-flight prefetch / demand-fetch misses /
        evict), dispatch the FFN against cache slots, then put the
        NEXT layer's predicted experts in flight while this FFN runs.
        ``l`` counts MoE layers (the store's layers), from 0.

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
            self._read("route", experts, layer=l).reshape(
                len(token_mask), -1)[token_mask].reshape(-1), minlength=E)
        slot_map = store.dispatch(l, counts, self.step_count)
        nb = store.take_rental_bytes()
        if nb and self.expert_runtime is not None:
            self.expert_runtime.record_rental(nb, source="expert_miss")
        # Experts that only masked rows route to have no slot (-1); point
        # them at row 0: those rows' outputs are zeroed by lane_mask, and a
        # -1 would be an out-of-bounds weight DMA (the TPU halts on it).
        slots = jnp.asarray(np.maximum(slot_map, 0))
        shared = self.params["layers"].get("shared")
        with span("repro.dispatch.ffn", layer=l):
            args = (x, h2, store.w_gate_cache, store.w_up_cache,
                    store.w_down_cache, slots, gates, experts, lane_mask)
            if shared is not None:
                args += (shared, jnp.int32(l))
            x = self._t_ffn(*args)
        # Double buffer: stage the next layer's predicted working set while
        # this grouped GEMM is in flight, forecast by speculative gating
        # (its router one attention delta early).  The callers of
        # ``_tiered_layers`` forecast the wrap-around to the first layer.
        nl = (l + 1) % self.model.cfg.moe_layers
        if nl:
            self._spec_prefetch(nl, lambda: x, token_mask)
        return x

    def _spec_prefetch(self, l, residual, token_mask):
        """Forecast layer ``l``'s routed experts from the residual stream
        (``residual()``, speculative gating) and put the likeliest
        non-resident ones in flight.  Live rows only — masked rows route
        garbage by design (see ``_tiered_ffn``).  The forecast covers the
        rows' top-k picks plus a small margin of next-likeliest experts by
        router mass: the margin costs overlapped (hidden) bytes and buys
        back the picks the missing attention delta flips.

        Where that is every expert, the router's mass would only order
        them, and reading it back waits for the FFN just enqueued: the
        store stages whole layers instead, several ahead, with no read
        (``ExpertStore.lead``)."""
        mcfg = self.model.moe_cfg
        live = int(np.sum(token_mask))
        if not live:
            return
        cap = min(mcfg.padded_experts, live * mcfg.top_k + 2)
        if cap == mcfg.padded_experts:
            self.expert_store.lead(self.step_count)
            return
        probs = self._read(
            "spec", self._t_spec_route(self.params, residual(), jnp.int32(l)),
            layer=l)
        mass = probs[token_mask].sum(axis=0)
        order = np.argsort(-mass, kind="stable")
        self.expert_store.prefetch(
            l, self.step_count, predicted=[int(e) for e in order[:cap]])

    def _tiered_layers(self, x, dispatch, frame_args, lane_mask, token_mask):
        """Every layer of one tiered dispatch (``dispatch`` "decode" or
        "prefill", ``frame_args`` what its frame takes): a leading dense
        layer is one piece; a MoE layer is its attention piece, then
        ``_tiered_ffn``.  ``token_mask`` is the host copy of the live rows.
        Re-binds ``self.pool.hbm`` after each piece (the pieces take it
        donated)."""
        mc = self.model.cfg
        nd, kind = mc.first_dense_layers, "mla" if mc.mla else "gqa"
        for l in range(mc.n_layers):
            args = (self.params, self.pool.hbm, x, jnp.int32(l), *frame_args)
            if l < nd:
                with span("repro.dispatch.dense", layer=l):
                    x, self.pool.hbm = self._t_dense(*args, dispatch=dispatch)
                continue
            with span("repro.dispatch.attn", layer=l, kind=kind):
                x, h2, gates, experts, self.pool.hbm = self._t_attn(
                    *args, dispatch=dispatch)
            x = self._tiered_ffn(l - nd, x, h2, gates, experts, lane_mask,
                                 token_mask)
        return x

    def _tiered_decode(self, tokens, page_table, lengths, write_slot,
                       write_off, active, seeds, temperature, top_k, top_p,
                       use_sampler, mask):
        """``mask`` is the host copy of ``active``."""
        x = self._tiered_layers(
            self._t_embed(self.params, tokens), "decode",
            (page_table, lengths, write_slot, write_off, active),
            active[:, None, None], mask)
        tail = self._t_tail_sampled if use_sampler else self._t_tail_greedy
        with span("repro.dispatch.tail"):
            logits, next_tokens = tail(self.params, x, lengths, active,
                                       seeds, temperature, top_k, top_p)
        # The sampled token IS the next step's layer-0 residual stream
        # (x = embed(token)): forecast the wrap-around to the next step's
        # first layer from it, hiding the fetch under the tail + host
        # scheduling gap.  Batch rotation between steps makes this a
        # forecast, not an oracle; mispredictions demand-fetch as usual.
        self._spec_prefetch(
            0, lambda: self._t_embed(self.params, next_tokens[:, None]), mask)
        return logits, next_tokens

    def _tiered_prefill(self, tokens, page_table, slots, offs, n_real,
                        start, mask):
        """``mask`` is the host copy of the prologue's ``valid``."""
        x, positions, lengths, valid = self._t_prefill_prologue(
            self.params, tokens, n_real, start)
        self._tiered_layers(
            x, "prefill", (page_table, slots, offs, positions, lengths,
                           valid), valid[None, :, None], mask)
        # No token follows a prefill to forecast the next step's first
        # dispatch from: the store falls back to recency + the guidance
        # profile.
        self.expert_store.prefetch(0, self.step_count)

    def _pools(self) -> tuple:
        """What a resident program takes donated: the K/V pools, then the
        recurrent state pool's arrays (a model with Mamba layers)."""
        return self.pool.hbm + (self.states.arrays if self.states else ())

    def _rebind(self, pools) -> None:
        """Re-bind the pools to what a program returned in their place."""
        n = len(self.pool.hbm)
        self.pool.hbm = tuple(pools[:n])
        if self.states is not None:
            self.states.arrays = tuple(pools[n:])

    def _read(self, what: str, value, **args) -> np.ndarray:
        """A blocking device->host read on the step path: the host waits
        here for the device work ``value`` depends on.  Counted in
        ``host_syncs`` and spanned ``repro.sync.<what>``."""
        self.host_syncs += 1
        with span(f"repro.sync.{what}", **args):
            return np.asarray(value)

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
        if self.states is not None:
            raise ValueError(
                f"cannot import request {rid} warm: its recurrent state does "
                f"not travel with its pages; import it cold (kv=None)")
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
            # A model with Mamba layers needs a free recurrent-state slot:
            # a paused request yields its slot (and pages) before the head
            # waits.
            if self.states is not None and not self.states.free:
                if not self._preempt_one():
                    return
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
        ``active`` via eager whole-suffix prefill (one ``_prefill_chunk``
        over the whole uncovered suffix), or — with chunked-prefill
        interleaving on — into ``prefilling``, where ``_advance_prefills``
        ingests budgeted chunks each step.  A model with Mamba layers
        takes its recurrent-state slot here."""
        if self.states is not None:
            rid = req.request_id
            with span("repro.engine.state", request_id=rid, op="alloc"):
                self.states.alloc(rid)
            if rid in self._dropped_state:
                self._dropped_state.discard(rid)
                self.ssm_recomputes += 1
        budget = self.scheduler.step_budget(self)
        if self.cfg.prefill == "one_shot" and budget.prefill_tokens > 0:
            self._begin_prefill(req)
        else:
            with span("repro.engine.prefill", request_id=req.request_id,
                      tokens=len(req.context) - 1):
                if self.cfg.prefill == "chunked":
                    self._prefill_request(req)
                else:
                    self._begin_prefill(req)
                    if req.state == "prefilling":
                        self._prefill_chunk(req, len(req.context) - 1
                                            - req.pos)
                        self._end_prefill(req)
            req.state = "active"
        req.last_scheduled = self.step_count

    def _begin_prefill(self, req: Request) -> None:
        """Start a prefill: consult the prefix cache, allocate
        the WHOLE uncovered suffix's pages now (admission already budgeted
        them — allocating lazily per chunk could lose the race against
        later admissions), and park the request in ``prefilling`` state.
        Trivial ingests (empty / full cache hit) go straight to active."""
        context = req.context
        n_ingest = len(context) - 1
        P = self.cfg.page_size
        rid = req.request_id
        chain = self._match_prefix(req, context, n_ingest) if n_ingest else []
        covered = len(chain) * P
        if covered == n_ingest:          # nothing to dispatch
            req.pos = n_ingest
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
            with span("repro.engine.prefill", request_id=req.request_id,
                      tokens=n):
                self._prefill_chunk(req, n)
            self.prefill_chunks += 1
            budget -= n
            self.scheduler.on_tokens(req, n, self)
            if req.pos >= n_ingest:
                self._end_prefill(req)

    def _end_prefill(self, req: Request) -> None:
        """The whole prompt is in: adopt its blocks into the prefix cache
        and make the request decode-eligible."""
        chain = self._pending_chains.pop(req.request_id, [])
        self._insert_prefix(req, req.context, len(req.context) - 1, chain)
        req.state = "active"
        req.last_scheduled = self.step_count

    def _prefill_chunk(self, req: Request, n: int) -> None:
        """Ingest ``req.context[req.pos : req.pos+n]`` with one bucketed
        dispatch at absolute start ``req.pos`` — eager prefill is one such
        chunk over the whole suffix, so every chunking of a prompt produces
        bitwise-identical K/V (rows attend by absolute length over the
        request's full page table).  A chunk longer than the dispatch cap
        goes as consecutive dispatches of the cap's bucket each."""
        cap = self._dispatch_cap
        S = cap if n > cap else None
        while n > 0:
            m = min(n, cap)
            self._prefill_dispatch(req, m, S)
            n -= m

    def _prefill_dispatch(self, req: Request, n: int,
                          S: Optional[int] = None) -> None:
        """One dispatch of ``_prefill_chunk``: ``n`` tokens padded to the
        bucket ``S`` (default the power of two at or above ``n``, at least
        a page)."""
        context = req.context
        P = self.cfg.page_size
        MP = self.cfg.max_pages_per_seq
        rid = req.request_id
        start = req.pos
        my_pages = self.pool.request_pages(rid)
        # The dispatch's table covers every page, so the whole sequence
        # must be HBM-resident (earlier chunks' pages may have been evicted
        # between steps — demand swap-in is a rental like any other).
        self._make_resident(
            [p.page_id for p in my_pages],
            [p.page_id for p in my_pages if p.hbm_slot is None])
        by_index = {p.index_in_seq: p for p in my_pages}
        # Pad the token axis to a power-of-two bucket (>= one page) so jit
        # compiles per bucket, not per chunk length.
        S = S or max(P, 1 << (n - 1).bit_length())
        tokens = np.zeros((S,), np.int32)
        tokens[:n] = context[start:start + n]
        slots = np.full((S,), self.scratch_slot, np.int32)
        offs = np.zeros((S,), np.int32)
        for t in range(n):
            idx, off = divmod(start + t, P)
            page = by_index[idx]
            slots[t] = page.hbm_slot
            offs[t] = off
            page.tokens_used = max(page.tokens_used, off + 1)
        table = np.full((MP,), -1, np.int32)
        for p in my_pages:
            table[p.index_in_seq] = p.hbm_slot
        # The resident single-jit scan, or the layer-by-layer tiered
        # pipeline: both write the pools in place and re-bind them.
        args = (jnp.asarray(tokens), jnp.asarray(table), jnp.asarray(slots),
                jnp.asarray(offs), jnp.int32(n), jnp.int32(start))
        if self.expert_store is not None:
            self._tiered_prefill(*args, mask=np.arange(S) < n)
        else:
            if self.states is not None:
                args += (jnp.int32(self.states.slot_of[rid]),)
            with span("repro.dispatch.step"):
                self._rebind(self._prefill(self.params, self._pools(), *args))
        req.pos = start + n
        for idx in range(start // P, (start + n - 1) // P + 1):
            if not by_index[idx].shared:      # the chunk's write set
                by_index[idx].accesses += 1
        self.prefill_dispatches += 1
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
        self._release_pages(victim.request_id, preempted=True)
        victim.pos = 0
        victim.state = "preempted"
        self.preemptions += 1
        return True

    def _release_pages(self, request_id: int, preempted: bool = False):
        """Drop every page reference the request holds, and its
        recurrent-state slot.  Shared prefix pages survive on the cache's
        reference; only pages that actually died leave the eviction
        policy's recommendation view.  ``preempted``: the request will be
        prefilled again from its context (preemption by recompute)."""
        freed = self.pool.release_request(request_id)
        if self.kv_backend is not None:
            self.kv_backend.forget_pages(freed)
        if self.states is not None and request_id in self.states.slot_of:
            with span("repro.engine.state", request_id=request_id,
                      op="free"):
                self.states.release(request_id)
            if preempted:
                self._dropped_state.add(request_id)

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
        self._release_pages(victim.request_id, preempted=True)
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
            with span("repro.kv.exchange", pages=len(missing)):
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
        """The chunked oracle (``prefill="chunked"``): ingest
        ``req.context[:-1]`` (the last token is fed by the first decode
        step) by stepping it through decode one token at a time.  The
        prefix cache is consulted first: matched blocks attach by reference
        and only the uncovered suffix is stepped (a FULL hit steps
        nothing)."""
        context = req.context
        n_ingest = len(context) - 1
        chain = self._match_prefix(req, context, n_ingest) if n_ingest else []
        covered = len(chain) * self.cfg.page_size
        req.pos = covered
        if covered == n_ingest:          # empty, or a full hit
            return
        for t in context[covered:-1]:
            self._decode_one(req, t)
        self.prefill_tokens += n_ingest - covered
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
        """Make the whole scheduled batch resident, with a free slot for
        each write page it lacks, then allocate those write pages."""
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
        self._make_resident(need_ids, missing, n_alloc)
        for r in reqs:
            idx = r.pos // P
            if idx >= len(self.pool.request_pages(r.request_id)):
                self.pool.allocate(r.request_id, idx, self.step_count)

    def _ensure_free_hbm(self, n: int, needed: List[int]):
        """Free ``n`` HBM slots by swapping out pages not in ``needed``."""
        self._make_resident(needed, [], n)

    def _make_resident(self, keep: Sequence[int], missing: Sequence[int],
                       n_free: int = 0) -> None:
        """Swap the ``missing`` pages in and leave ``n_free`` more HBM slots
        free, evicting the policy's victims among pages not in ``keep``.
        ONE atomic batched exchange: evictions and swap-ins stage together,
        so it succeeds even when both free lists are empty (an
        evict-then-swap-in order would need host slots that a
        tightly-sized pool does not have)."""
        shortfall = len(missing) + n_free - len(self.pool.free_hbm)
        victims: List[int] = []
        if shortfall > 0:
            exclude = set(keep)
            cands = [p for p in self.pool.pages.values()
                     if p.hbm_slot is not None and p.page_id not in exclude]
            victims = self.eviction.pick_many(cands, self, shortfall)
            if len(victims) < shortfall:
                # Unreachable under scheduler budgets.
                raise MemoryError("no evictable page")
        if victims or missing:
            with span("repro.kv.exchange",
                      pages=len(victims) + len(missing)):
                self.pool.exchange(victims, missing)
            self._note_swap_in(len(missing))

    # ============================================================ stepping
    def _decode_one(self, req: Request, token: int) -> int:
        """Single-request decode (the chunked-prefill oracle path)."""
        self._prepare_batch([req])
        self.prefill_dispatches += 1
        with span("repro.engine.decode", rows=1):
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
        with span("repro.engine.step", step=self.step_count):
            self.scheduler.on_step(self)
            with span("repro.engine.admit"):
                self._admit_waiting()
                self._advance_prefills()
            with span("repro.engine.schedule"):
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
                with span("repro.engine.decode", rows=len(pairs)):
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
            logits, toks = self._tiered_decode(
                *args, use_sampler=not greedy, mask=active)
        else:
            if self.states is not None:
                slots = np.full((B,), self.states.scratch, np.int32)
                for i, (req, _) in enumerate(pairs):
                    slots[i] = self.states.slot_of[req.request_id]
                args += (jnp.asarray(slots),)
            decode = self._decode_greedy if greedy else self._decode_sampled
            with span("repro.dispatch.step"):
                logits, toks, pools = decode(self.params, self._pools(),
                                             *args)
            self._rebind(pools)
        if self.cfg.keep_logits:
            logits_np = self._read("tokens", logits)
            for i, (req, _) in enumerate(pairs):
                self.last_logits[req.request_id] = logits_np[i]
        toks = self._read("tokens", toks)
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
            "expert_needed_blocks": es.needed_blocks,
            "expert_resident_hits": es.resident_hits,
            "expert_committed_blocks": es.committed_blocks,
            "expert_landed_blocks": es.landed_blocks,
        } if es is not None else {}
        st = self.states
        state = {
            "ssm_slots_live": st.live(),
            "ssm_state_bytes": sum(a.size * a.dtype.itemsize
                                   for a in st.arrays),
            "ssm_recomputes": self.ssm_recomputes,
        } if st is not None else {}
        return {
            **prefix,
            **expert,
            **state,
            "saved_prefill_tokens": self.saved_prefill_tokens,
            "steps": self.step_count,
            "host_syncs": self.host_syncs,
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
