"""Backend-conformance suite for the unified Algorithm-1 API.

Every ``TierBackend`` is driven by the same ``GuidanceRuntime`` loop, so each
backend is checked two ways on a fixed trace:

1. **Protocol conformance** — snapshot/telemetry/reweight/enforce invariants
   (unique arena ids, telemetry consistent with the profile, counters scaled
   by decay, capacity respected after enforcement).

2. **Decision parity with the pre-refactor loops** — the reference functions
   below are transliterations of the seed implementations this API replaced
   (the seed controller's ``maybe_migrate``, ``MemSimulator._online_decide``'s
   fragmentation arm, ``Engine._gdt_interval``).  They are pure reads of
   backend state, so at each interval the reference runs first and the
   runtime's recorded ``MigrationPlan`` must match it exactly.

Backends covered parametrically: ``ArenaBackend`` (trainer path),
``SimArenaBackend`` (simulator path, fragmented telemetry) and
``PagedKVBackend`` (serving path, page chunks) — plus the capacity fix at
the ``PagedKVBackend.enforce`` boundary.
"""


import pytest

from repro.core import (
    CLX,
    ArenaBackend,
    ArenaManager,
    FractionPlacer,
    GuidanceConfig,
    GuidanceRuntime,
    SiteKind,
    SiteRegistry,
    collapse_to_chunks,
    decide,
    explode_profile,
    parent_fractions,
    recommend,
)
from repro.core.profiler import ArenaProfile, IntervalProfile
from repro.core.runtime import MigrationPlan, MoveStats
from repro.mem.simulator import SimArenaBackend
from repro.mem import SimSite, SimWorkload

MB = 2**20


# ====================================================== reference loops
def profile_of(arenas: ArenaManager) -> IntervalProfile:
    """Pure snapshot of arena state (what OnlineProfiler reports)."""
    rows = [
        ArenaProfile(arena_id=a.arena_id, site_id=a.site.site_id,
                     label=a.site.label, accesses=a.accesses,
                     resident_bytes=a.resident_bytes,
                     fast_fraction=a.fast_fraction)
        for a in arenas
    ]
    return IntervalProfile(0, rows, arenas.private_pool_bytes, 0.0)


def reference_plain(arenas, hw, cap, strategy):
    """The seed controller's maybe_migrate: profile -> recommend -> decide."""
    profile = profile_of(arenas)
    recs = recommend(profile, cap, strategy)
    decision = decide(profile, recs, hw)
    return decision, dict(recs.fractions), {}


def reference_fragmented(arenas, telemetry, hw, cap, strategy, num_fragments):
    """Seed fragmentation arm (simulator ``_online_decide`` / engine
    ``_gdt_interval``): explode -> recommend -> decide -> collapse."""
    profile = profile_of(arenas)
    exploded, frags = explode_profile(profile, telemetry,
                                      num_fragments=num_fragments)
    recs = recommend(exploded, cap, strategy)
    decision = decide(exploded, recs, hw)
    placement = collapse_to_chunks(frags, recs.fractions)
    pf = parent_fractions(frags, placement)
    fractions = {
        a.arena_id: pf.get(a.arena_id,
                           recs.fractions.get(a.arena_id, 0.0))
        for a in arenas
    }
    return decision, fractions, placement


# ========================================================= harnesses
class Harness:
    """One backend + runtime + a fixed access trace + its reference loop."""

    def __init__(self, name, backend, runtime, touch, reference):
        self.name = name
        self.backend = backend
        self.runtime = runtime
        self.touch = touch          # touch(i): apply interval i's accesses
        self.reference = reference  # () -> (decision, fractions, placement)


def make_arena_harness():
    reg = SiteRegistry()
    cap = 50 * MB
    mgr = ArenaManager(reg, promotion_threshold=1 * MB,
                       fast_capacity_bytes=cap)
    hot = reg.register(["hot"], SiteKind.PARAM)
    cold = reg.register(["cold"], SiteKind.PARAM)
    mgr.allocate(cold, 40 * MB)     # first-touch: cold grabs the fast tier
    mgr.allocate(hot, 40 * MB)
    backend = ArenaBackend(mgr, CLX)
    runtime = GuidanceRuntime(
        backend, CLX, GuidanceConfig(strategy="thermos",
                                     fast_capacity_bytes=cap,
                                     interval_steps=1))

    def touch(i):
        mgr.touch(hot, 400_000)
        mgr.touch(cold, 10)

    def reference():
        return reference_plain(mgr, CLX, cap, "thermos")

    return Harness("arena", backend, runtime, touch, reference)


def make_sim_harness():
    sites = [
        SimSite("big_skewed", nbytes=60 * MB, read_GBps=8.0,
                hot_page_frac=0.3, hot_traffic_frac=0.9),
        SimSite("uniform", nbytes=30 * MB, read_GBps=2.0),
    ]
    wl = SimWorkload("conformance", sites, phases=8)
    reg = SiteRegistry()
    cap = 40 * MB
    mgr = ArenaManager(reg, fast_capacity_bytes=cap)
    core_sites = {s.name: reg.register([s.name], SiteKind.OTHER)
                  for s in sites}
    arena_of = {s.name: mgr.allocate(core_sites[s.name], s.nbytes)
                for s in sites}
    backend = SimArenaBackend(mgr, CLX, FractionPlacer(mgr), wl, arena_of,
                              fragmentation=True)
    runtime = GuidanceRuntime(
        backend, CLX, GuidanceConfig(strategy="thermos",
                                     fast_capacity_bytes=cap,
                                     interval_steps=1, num_fragments=2))

    def touch(i):
        # Phase shift: the skewed site dominates early, then the uniform
        # site becomes the hot set and must be promoted over it.
        if i < 3:
            mgr.touch(core_sites["big_skewed"], 900_000)
            mgr.touch(core_sites["uniform"], 120_000)
        else:
            mgr.touch(core_sites["big_skewed"], 90_000)
            mgr.touch(core_sites["uniform"], 5_000_000)

    def reference():
        # Rebuild the telemetry exactly as the backend will (pure read).
        profile = profile_of(mgr)
        by_arena = profile.by_arena()
        telem = {}
        for s in wl.sites:
            arena = arena_of[s.name]
            if s.hot_page_frac >= 1.0:
                continue
            row = by_arena[arena.arena_id]
            hot_b = int(s.nbytes * s.hot_page_frac)
            from repro.core import ChunkStats
            telem[arena.arena_id] = [
                ChunkStats(chunk_id=arena.arena_id * 2, nbytes=hot_b,
                           accesses=int(row.accesses * s.hot_traffic_frac),
                           age=0, fast=row.fast_fraction > 0.5),
                ChunkStats(chunk_id=arena.arena_id * 2 + 1,
                           nbytes=s.nbytes - hot_b,
                           accesses=int(row.accesses * (1 - s.hot_traffic_frac)),
                           age=1, fast=False),
            ]
        return reference_fragmented(mgr, telem, CLX, cap, "thermos", 2)

    return Harness("sim", backend, runtime, touch, reference)


def make_paged_harness():
    from repro.serve import PagedKVBackend
    from repro.serve.kvcache import PagedKVPool

    pool = PagedKVPool(n_layers=2, page_size=4, kv_heads=2, head_dim=8,
                       hbm_pages=6, host_pages=16)
    pool.free_hbm.pop(0)            # engine-style reserved scratch slot
    requests = {0: object(), 1: object()}
    for rid in (0, 1):
        for idx in range(2):
            pool.allocate(rid, idx, step=0)
    # One cold page starts on the host tier.
    pool.swap_out(pool.request_pages(1)[1].page_id)
    clock = {"step": 0}
    backend = PagedKVBackend(pool, requests, clock=lambda: clock["step"])
    cap = 5 * pool.page_bytes       # hbm_pages minus the scratch slot
    runtime = GuidanceRuntime(
        backend, CLX, GuidanceConfig(strategy="thermos",
                                     fast_capacity_bytes=cap,
                                     interval_steps=1, num_fragments=4,
                                     skip_empty_intervals=True),
        clock=lambda: clock["step"])

    def touch(i):
        clock["step"] = i + 1
        for p in pool.request_pages(0):
            p.accesses += 50        # request 0 is hot
        for p in pool.request_pages(1):
            p.accesses += 2

    def reference():
        # Transliteration of the seed Engine._gdt_interval (pure read).
        from repro.core import ChunkStats
        rows, telem = [], {}
        pb = pool.page_bytes
        for rid in requests:
            pages = pool.request_pages(rid)
            if not pages:
                continue
            fast_b = sum(1 for p in pages if p.hbm_slot is not None)
            rows.append(ArenaProfile(
                arena_id=rid, site_id=rid, label=f"req{rid}",
                accesses=sum(p.accesses for p in pages),
                resident_bytes=len(pages) * pb,
                fast_fraction=fast_b / len(pages)))
            telem[rid] = [
                ChunkStats(chunk_id=p.page_id, nbytes=pb,
                           accesses=p.accesses,
                           age=clock["step"] - p.birth_step,
                           fast=p.hbm_slot is not None)
                for p in pages]
        profile = IntervalProfile(clock["step"], rows, 0, 0.0)
        exploded, frags = explode_profile(profile, telem, num_fragments=4)
        recs = recommend(exploded, cap, "thermos")
        decision = decide(exploded, recs, CLX)
        placement = collapse_to_chunks(frags, recs.fractions)
        return decision, None, placement

    return Harness("paged", backend, runtime, touch, reference)


def make_expert_harness():
    import jax
    import jax.numpy as jnp
    from repro.serve import ExpertBackend, ExpertStore

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    moe_params = {
        "w_gate": jax.random.normal(ks[0], (2, 4, 4, 4), jnp.float32),
        "w_up": jax.random.normal(ks[1], (2, 4, 4, 4), jnp.float32),
        "w_down": jax.random.normal(ks[2], (2, 4, 4, 4), jnp.float32),
    }
    store = ExpertStore(moe_params, 2, 4, 2)
    clock = {"step": 0}
    backend = ExpertBackend(store, clock=lambda: clock["step"])
    cap = store.cache_bytes
    runtime = GuidanceRuntime(
        backend, CLX, GuidanceConfig(strategy="thermos",
                                     fast_capacity_bytes=cap,
                                     interval_steps=1, num_fragments=4,
                                     skip_empty_intervals=True),
        clock=lambda: clock["step"])

    def touch(i):
        # Phase shift: layer 0's experts dominate the routed-token counts
        # early, then layer 1 becomes the hot population and its blocks
        # must be promoted over layer 0's.
        clock["step"] = i + 1
        hot = 0 if i < 3 else 1
        for e in range(store.n_experts):
            store.blocks[(hot, e)].accesses += 200.0 + 50.0 * e
            store.blocks[(1 - hot, e)].accesses += 1.0

    def reference():
        # Transliteration of ExpertBackend.snapshot + the engine interval
        # loop (pure read): layer arenas, per-block chunk telemetry.
        from repro.core import ChunkStats
        step = clock["step"]
        bb = store.block_bytes
        rows, telem = [], {}
        for l in range(store.n_layers):
            blocks = [store.blocks[(l, e)] for e in range(store.n_experts)]
            fast = sum(1 for b in blocks if b.slot is not None)
            rows.append(ArenaProfile(
                arena_id=l, site_id=l, label=f"moe_l{l}",
                accesses=sum(b.accesses for b in blocks),
                resident_bytes=len(blocks) * bb,
                fast_fraction=fast / len(blocks)))
            telem[l] = [
                ChunkStats(chunk_id=store.chunk_id(l, b.expert), nbytes=bb,
                           accesses=b.accesses, age=step - b.birth_step,
                           fast=b.slot is not None)
                for b in blocks]
        profile = IntervalProfile(step, rows, 0, 0.0)
        exploded, frags = explode_profile(profile, telem, num_fragments=4)
        recs = recommend(exploded, cap, "thermos")
        decision = decide(exploded, recs, CLX)
        placement = collapse_to_chunks(frags, recs.fractions)
        return decision, None, placement

    return Harness("expert", backend, runtime, touch, reference)


HARNESSES = {
    "arena": make_arena_harness,
    "sim": make_sim_harness,
    "paged": make_paged_harness,
    "expert": make_expert_harness,
}


@pytest.fixture(params=sorted(HARNESSES))
def harness(request):
    return HARNESSES[request.param]()


# ==================================================== protocol conformance
def test_snapshot_invariants(harness):
    harness.touch(0)
    profile = harness.backend.snapshot()
    ids = [r.arena_id for r in profile.rows]
    assert len(ids) == len(set(ids)), "duplicate arena ids"
    assert profile.rows, "fixed trace must produce a non-empty profile"
    for r in profile.rows:
        assert 0.0 <= r.fast_fraction <= 1.0
        assert r.resident_bytes >= 0
        assert r.accesses >= 0


def test_telemetry_consistent_with_profile(harness):
    harness.touch(0)
    profile = harness.backend.snapshot()
    telemetry = harness.backend.telemetry()
    by_arena = profile.by_arena()
    for arena_id, chunks in telemetry.items():
        assert arena_id in by_arena, "telemetry for unknown arena"
        assert sum(c.nbytes for c in chunks) == by_arena[arena_id].resident_bytes
        ids = [c.chunk_id for c in chunks]
        assert len(ids) == len(set(ids))


def test_reweight_scales_access_counters(harness):
    harness.touch(0)
    before = {r.arena_id: r.accesses
              for r in harness.backend.snapshot().rows}
    harness.backend.reweight(0.5)
    after = {r.arena_id: r.accesses
             for r in harness.backend.snapshot().rows}
    for arena_id, accs in before.items():
        assert after[arena_id] <= accs // 2 + len(after), \
            "reweight must decay every profiled counter"


# =============================================== parity with the seed loop
def test_decisions_match_pre_refactor_loop(harness):
    """Fixed trace, interval by interval: the runtime must reproduce the
    seed loop's ski-rental decision, target fractions and chunk placement."""
    migrated_any = False
    for i in range(8):
        harness.touch(i)
        want_decision, want_fractions, want_placement = harness.reference()
        event = harness.runtime.maybe_migrate()
        assert event is not None
        assert event.decision == want_decision, f"interval {i}"
        assert event.migrated == want_decision.migrate
        if want_placement:
            assert event.plan.chunk_placement == want_placement, f"interval {i}"
        if want_fractions is not None and event.migrated:
            for arena_id, frac in want_fractions.items():
                assert event.plan.fast_fraction(arena_id) == pytest.approx(frac)
        migrated_any = migrated_any or event.migrated
    assert migrated_any, "trace must exercise at least one migration"


def test_capacity_respected_after_enforcement(harness):
    cap = harness.runtime.config.fast_capacity_bytes
    for i in range(8):
        harness.touch(i)
        harness.runtime.maybe_migrate()
    fast = getattr(harness.backend, "fast_bytes", lambda: 0)()
    assert fast <= cap, f"{harness.name}: fast tier over budget"


def test_event_stream_is_structured(harness):
    for i in range(4):
        harness.touch(i)
        harness.runtime.maybe_migrate()
    events = harness.runtime.events
    assert len(harness.runtime.history) == 4
    assert all(e.kind == "interval" for e in harness.runtime.history)
    assert harness.runtime.total_bytes_migrated == sum(
        e.bytes_moved for e in harness.runtime.history)
    # The summary consumer digests the stream without touching backends.
    from repro.launch.analysis import guidance_summary

    summary = guidance_summary(events)
    assert summary["intervals"] == 4
    assert summary["migrations"] == harness.runtime.migration_count


# ===================================== serving capacity fix (API boundary)
def test_paged_enforce_refuses_overfull_promotions():
    """The seed engine silently dropped promotions when HBM was full,
    desynchronizing ``last_recs`` from reality.  ``PagedKVBackend.enforce``
    must refuse the excess, report it, and keep ``last_recs`` truthful."""
    from repro.serve import PagedKVBackend
    from repro.serve.kvcache import PagedKVPool

    pool = PagedKVPool(n_layers=1, page_size=2, kv_heads=1, head_dim=4,
                       hbm_pages=4, host_pages=8)
    pool.free_hbm.pop(0)            # reserved scratch slot
    requests = {0: object()}
    # Three pages on the host tier, all "recommended fast" (allocated first
    # and swapped straight out so the HBM slots stay free for the residents).
    hosted = []
    for i in range(3):
        p = pool.allocate(0, i, step=0)
        pool.swap_out(p.page_id)
        hosted.append(p)
    resident = [pool.allocate(0, 3 + i, step=0) for i in range(3)]
    assert pool.free_hbm == []      # HBM full: 3 resident + scratch

    backend = PagedKVBackend(pool, requests, clock=lambda: 1)
    placement = {p.page_id: True for p in resident + hosted}
    backend.last_recs = dict(placement)
    plan = MigrationPlan(
        profile=IntervalProfile(1, [], 0, 0.0),
        exploded=IntervalProfile(1, [], 0, 0.0),
        fragments=[], assignment=None, decision=None,
        fractions={}, chunk_placement=placement,
        capacity_bytes=3 * pool.page_bytes, strategy="thermos")
    stats = backend.enforce(plan)

    assert isinstance(stats, MoveStats)
    assert stats.bytes_promoted == 0, "no free slot -> no promotion"
    assert stats.dropped_promotions == 3
    # last_recs now reflects the placement that actually exists.
    for p in hosted:
        assert backend.last_recs[p.page_id] is False
        assert pool.pages[p.page_id].hbm_slot is None
    for p in resident:
        assert backend.last_recs[p.page_id] is True
        assert pool.pages[p.page_id].hbm_slot is not None
