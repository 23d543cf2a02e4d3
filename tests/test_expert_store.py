"""Guided expert-weight tiering: store semantics + engine bitwise parity.

The contract under test (DESIGN.md Sec. 15): serving MoE expert FFN
weights out of a bounded HBM cache is a *placement* change, never a
*results* change — streams and logits are bitwise-equal to the fully
resident path whenever each dispatch's working set fits, across eviction
churn, double-buffered prefetch, chunked prefill and preemption; a
working set that cannot fit raises a named error citing the knob.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import build_model
from repro.serve import (
    LLM,
    ExpertCacheMissError,
    ExpertStore,
    SamplingParams,
    ServeConfig,
)

# ================================================== store-level unit tests
L, E, D, F = 2, 4, 4, 4


def make_store(cache_slots, n_layers=L, store=ExpertStore):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    moe_params = {
        "w_gate": jax.random.normal(ks[0], (n_layers, E, D, F), jnp.float32),
        "w_up": jax.random.normal(ks[1], (n_layers, E, D, F), jnp.float32),
        "w_down": jax.random.normal(ks[2], (n_layers, E, F, D), jnp.float32),
    }
    return moe_params, store(moe_params, n_layers, E, cache_slots)


def counts(*experts):
    c = np.zeros(E, dtype=np.int64)
    for e in experts:
        c[e] += 1
    return c


def test_host_tier_roundtrip_keeps_memory_kinds_and_bits():
    """Every block's authoritative copy is a pinned_host array per weight,
    bitwise the caller's block; a dispatch lands it in the device-kind
    cache bitwise."""
    params, st = make_store(4)
    names = ("w_gate", "w_up", "w_down")
    assert st.host.memory_kinds() == {"pinned_host"}
    for l in range(L):
        for e in range(E):
            rows = st.host.read([st.chunk_id(l, e)])
            for (row,), name in zip(rows, names):
                assert np.array_equal(row, np.asarray(params[name][l, e]))
    slot_map = st.dispatch(1, counts(0, 3), step=1)
    caches = (st.w_gate_cache, st.w_up_cache, st.w_down_cache)
    for e in (0, 3):
        for cache, name in zip(caches, names):
            assert cache.sharding.memory_kind == "device"
            assert np.array_equal(np.asarray(cache[slot_map[e]]),
                                  np.asarray(params[name][1, e]))


def test_init_rejects_zero_slots():
    with pytest.raises(ValueError, match="expert_cache_size"):
        make_store(0)


def test_dispatch_installs_and_maps_slots():
    params, st = make_store(4)
    slot_map = st.dispatch(0, counts(0, 2), step=1)
    assert st.is_resident(0, 0) and st.is_resident(0, 2)
    assert slot_map[1] == -1 and slot_map[3] == -1
    assert st.demand_fetches == 2
    # the cache rows hold bitwise copies of the host blocks
    wg = np.asarray(params["w_gate"])
    cache = np.asarray(st.w_gate_cache)
    assert np.array_equal(cache[slot_map[0]], wg[0, 0])
    assert np.array_equal(cache[slot_map[2]], wg[0, 2])


def test_lru_eviction_prefers_oldest():
    _, st = make_store(2)
    st.dispatch(0, counts(0, 1), step=1)
    st.dispatch(0, counts(1), step=2)          # refresh (0,1), (0,0) is LRU
    st.dispatch(1, counts(3), step=3)          # needs a slot: evict (0,0)
    assert not st.is_resident(0, 0)
    assert st.is_resident(0, 1) and st.is_resident(1, 3)
    assert st.evictions == 1


def test_working_set_overflow_raises_named_error():
    _, st = make_store(2)
    with pytest.raises(ExpertCacheMissError, match="expert_cache_size"):
        st.dispatch(0, counts(0, 1, 2), step=1)


def test_prefetch_hit_skips_demand_and_miss_falls_back():
    _, st = make_store(4)
    st.dispatch(0, counts(0, 1), step=1)
    assert st.prefetch(1, step=1, predicted=[0, 1]) == 2
    st.dispatch(1, counts(0, 2), step=1)
    assert st.prefetch_fetches == 2
    assert st.prefetch_hits == 1               # predicted 0, routed {0, 2}
    assert st.demand_fetches == 3              # (0,0) (0,1) + fallback (1,2)


def test_prefetch_never_evicts_pins_or_its_own_forecast():
    _, st = make_store(2)
    st.dispatch(0, counts(0, 1), step=1)       # both slots pinned
    assert st.prefetch(1, step=1, predicted=[2, 3]) == 0
    assert st.dropped_prefetches == 2
    assert st.is_resident(0, 0) and st.is_resident(0, 1)


def test_drop_many_refuses_dispatching_blocks():
    _, st = make_store(4)
    st.dispatch(0, counts(0, 1), step=1)
    st.fetch_many([(1, 3)], step=1)            # controller promote
    dropped = st.drop_many([(0, 0), (1, 3)])
    assert dropped == [(1, 3)], \
        "a block named in its layer's last dispatch must never demote"
    assert st.is_resident(0, 0) and not st.is_resident(1, 3)


def test_fetch_many_uses_free_slots_only():
    _, st = make_store(2)
    st.dispatch(0, counts(0, 1), step=1)       # cache full
    done, refused = st.fetch_many([(1, 2)], step=2)
    assert done == [] and refused == [(1, 2)], \
        "controller promotion must never evict"


def test_demotion_is_metadata_only_and_refetch_is_bitwise():
    params, st = make_store(2)
    m0 = st.dispatch(0, counts(0), step=1)
    first = np.asarray(st.w_down_cache)[m0[0]].copy()
    st.dispatch(1, counts(1, 2), step=2)       # evicts (0,0)
    assert st.bytes_fetched == 3 * st.block_bytes
    m1 = st.dispatch(0, counts(0), step=3)     # refetch from host tier
    again = np.asarray(st.w_down_cache)[m1[0]]
    assert np.array_equal(first, again)
    assert np.array_equal(again, np.asarray(params["w_down"])[0, 0])


class LRUStore(ExpertStore):
    """The store with the plain least-recently-used victim order, the
    baseline the next-use order is measured against."""

    def _evictable(self, protect, layer):
        out = [b for b in self.blocks.values()
               if b.slot is not None and b.slot not in protect
               and b.slot not in self._reserved]
        out.sort(key=lambda b: (b.last_used, b.layer, b.expert))
        return out


def decode_step(st, step, route):
    """One decode step in the tiered pipeline's order: dispatch layer l,
    then prefetch layer l + 1 with its routed set as the forecast; the
    wrap-around prefetch of layer 0 last.  Returns the blocks fetched."""
    before = st.prefetch_fetches + st.demand_fetches
    n = st.n_layers
    for l in range(n):
        st.dispatch(l, counts(*route(step, l)), step=step)
        nl = (l + 1) % n
        if nl:
            st.prefetch(nl, step, predicted=route(step, nl))
    st.prefetch(0, step, predicted=route(step + 1, 0))
    return st.prefetch_fetches + st.demand_fetches - before


def test_cyclic_scan_keeps_whole_layers_resident_across_steps():
    """Every layer routes all E experts and the cache holds half the
    blocks: LRU evicts each block just before its next use, the next-use
    order streams about half the layers and keeps the rest whole."""
    n, slots = 8, 16
    _, st = make_store(slots, n_layers=n)
    decode_step(st, 0, lambda s, l: range(E))              # warm-up
    for step in range(1, 6):
        st.reset_counters()
        fetched = decode_step(st, step, lambda s, l: range(E))
        assert fetched <= n * E - slots + 2 * E, step
        assert st.demand_fetches == 0
        assert 0 < st.resident_hits <= st.needed_blocks == n * E
        for l in range(n):
            held = sum(st.is_resident(l, e) for e in range(E))
            assert held in (0, E), (step, l, held)
    _, lru = make_store(slots, n_layers=n, store=LRUStore)
    decode_step(lru, 0, lambda s, l: range(E))
    lru.reset_counters()
    assert decode_step(lru, 1, lambda s, l: range(E)) == n * E
    assert lru.resident_hits == 0


def test_skewed_routing_that_fits_fetches_nothing_after_warm_up():
    """Every layer reuses experts 0 and 1, and in the first four steps two
    layers a step also route one more: the hot set fits the cache, the
    next-use order fetches and evicts what LRU does, and once the extras
    stop nothing moves."""
    n, slots = 8, 20
    rng = np.random.default_rng(4)
    extra = {(s, int(l)): int(rng.integers(2, E))
             for s in range(4) for l in rng.choice(n, 2, replace=False)}

    def route(step, l):
        return [0, 1] + ([extra[(step, l)]] if (step, l) in extra else [])

    runs = []
    for cls in (ExpertStore, LRUStore):
        _, st = make_store(slots, n_layers=n, store=cls)
        fetched = [decode_step(st, step, route) for step in range(9)]
        assert fetched[4:] == [0] * 5, fetched
        runs.append((fetched, st.evictions))
    assert runs[0] == runs[1]
    assert runs[0][1] > 0


def test_cold_block_is_evicted_before_any_hot_block():
    _, st = make_store(3)
    st.dispatch(0, counts(0), step=1)          # (0,0) hot, oldest
    st.dispatch(1, counts(0, 1), step=2)
    st.dispatch(1, counts(1), step=3)          # (1,0) cold, newer
    st.dispatch(1, counts(1, 2), step=4)       # needs a slot
    assert st.is_resident(0, 0) and not st.is_resident(1, 0)
    assert st.evictions == 1


def test_hot_blocks_go_farthest_next_use_first():
    _, st = make_store(4, n_layers=4)
    for l in range(4):
        st.dispatch(l, counts(0), step=1)      # every block hot, cache full
    # Serving layer 1 (expert 1): layer 0's next dispatch is the farthest
    # in the cycle 1, 2, 3, 0, though its block is the least recent.
    st.dispatch(1, counts(0, 1), step=2)
    assert not st.is_resident(0, 0)
    assert all(st.is_resident(l, 0) for l in (1, 2, 3))


def test_resident_hits_count_neither_prefetched_nor_demanded_blocks():
    _, st = make_store(4)
    st.dispatch(0, counts(0, 1), step=1)       # two demand fetches
    st.prefetch(1, step=1, predicted=[2])
    st.dispatch(1, counts(2), step=1)          # committed prefetch
    st.dispatch(0, counts(0, 1), step=2)       # both resident already
    assert st.needed_blocks == 5
    assert st.resident_hits == 2
    st.reset_counters()
    assert st.needed_blocks == st.resident_hits == 0


def lead_step(st, step, route, check=None):
    """One decode step in the tiered pipeline's order when the forecast
    names every expert: dispatch layer l, then ``lead`` (after the last
    layer, the wrap-around's).  ``check(before, after)`` sees the store
    around every ``lead``.  Returns the blocks fetched."""
    before = st.prefetch_fetches + st.demand_fetches
    for l in range(st.n_layers):
        st.dispatch(l, counts(*route(step, l)), step=step)
        snap = residency(st)
        st.lead(step)
        if check is not None:
            check(snap, residency(st))
    return st.prefetch_fetches + st.demand_fetches - before


def residency(st):
    return {(l, e) for (l, e), b in st.blocks.items() if b.slot is not None}


def staged_layers(st):
    return set(st._pending) | set(st._ahead)


def missing_order(st, resident):
    """Layers in cycle order from the next dispatch that miss a block."""
    n = st.n_layers
    cycle = [(st._next + d) % n for d in range(n)]
    return [m for m in cycle
            if any((m, e) not in resident for e in range(E))]


def test_lead_stages_the_next_two_missing_layers_and_skips_resident_ones():
    n, slots = 8, 16
    _, st = make_store(slots, n_layers=n)
    seen = []

    def check(before, after):
        staged = staged_layers(st)
        assert staged == set(missing_order(st, before)[:2]), staged
        seen.append(staged)

    every = lambda s, l: range(E)
    lead_step(st, 0, every)                                 # warm-up
    for step in range(1, 4):
        lead_step(st, step, every, check)
    # Resident layers were skipped: some lead staged a layer beyond the
    # next dispatch.
    assert any(m != st._next for s in seen for m in s)


def test_lead_never_holds_more_than_two_slabs():
    n, slots = 8, 16
    rng = np.random.default_rng(7)
    _, st = make_store(slots, n_layers=n)
    sizes = []

    def check(before, after):
        sizes.append(len(staged_layers(st)))

    for step in range(6):
        route = {l: rng.choice(E, rng.integers(1, E + 1), replace=False)
                 for l in range(n)}
        lead_step(st, step, lambda s, l: route[l], check)
    assert max(sizes) == 2


def test_lead_never_evicts_a_block_of_a_layer_dispatching_before_a_staged_one():
    n, slots = 8, 16
    _, st = make_store(slots, n_layers=n)

    def check(before, after):
        cycle = [(st._next + d) % n for d in range(n)]
        last = max(cycle.index(m) for m in staged_layers(st))
        for m in cycle[:last]:
            lost = {(l, e) for l, e in before - after if l == m}
            assert not lost, (m, lost)

    for step in range(5):
        lead_step(st, step, lambda s, l: range(E), check)


def test_lead_fetches_what_the_one_layer_lead_fetches():
    """The same blocks move as under the one-layer lead, within the cyclic
    scan's bound, with no demand fetch: only when their slabs go on the
    link changes.  Every committed block is counted, landed or not."""
    n, slots = 8, 16
    runs = []
    for step_fn in (decode_step, lead_step):
        _, st = make_store(slots, n_layers=n)
        fetched, hits = [], []
        for step in range(6):
            st.reset_counters()
            fetched.append(step_fn(st, step, lambda s, l: range(E)))
            hits.append(st.resident_hits)
            assert st.demand_fetches == 0 or step == 0
            assert 0 <= st.landed_blocks <= st.committed_blocks
        assert all(f <= n * E - slots + 2 * E for f in fetched[1:])
        runs.append((fetched, hits, st.bytes_fetched))
    assert runs[0] == runs[1]


@pytest.mark.parametrize("floor", [E, E - 1])
def test_tight_cache_never_raises_where_the_one_layer_lead_did_not(floor):
    """The cache at the decode floor plus one layer, routing of up to
    ``floor`` experts a layer: staging two slabs ahead never turns a
    dispatch the one-layer lead served into an ``ExpertCacheMissError``."""
    n = 6
    for seed in range(4):
        rng = np.random.default_rng(seed)
        routes = [{l: rng.choice(E, rng.integers(1, floor + 1),
                                 replace=False) for l in range(n)}
                  for _ in range(6)]
        outcome = []
        for step_fn in (decode_step, lead_step):
            _, st = make_store(floor + E, n_layers=n)
            try:
                for step, route in enumerate(routes):
                    step_fn(st, step, lambda s, l: route[l])
                outcome.append(None)
            except ExpertCacheMissError as e:
                outcome.append(e)
        assert outcome == [None, None], outcome


# ============================================== engine-level equivalence
@pytest.fixture(scope="module")
def moe_model():
    cfg = dataclasses.replace(get_smoke("granite_moe_3b_a800m"),
                              remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_llm(moe_model, **kw):
    model, params = moe_model
    return LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=32, host_pages=64,
        max_pages_per_seq=16, interval_steps=4, keep_logits=True, **kw))


PROMPTS = [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8], [1, 6, 1, 8, 0, 3]]
PLIST = [SamplingParams(max_tokens=6),
         SamplingParams(max_tokens=6, temperature=0.8, top_k=4, seed=7),
         SamplingParams(max_tokens=6, temperature=1.1, top_p=0.9, seed=3)]


def drive(llm, prompts, params_list):
    """Drive generation by hand, capturing every step's logits per row."""
    handles = [llm.submit(p, sp) for p, sp in zip(prompts, params_list)]
    logits = {h.request_id: [] for h in handles}
    while any(not h.finished for h in handles):
        out = llm.step()
        for rid in out:
            if rid in llm.engine.last_logits:
                logits[rid].append(llm.engine.last_logits[rid].copy())
    return [h.result() for h in handles], logits


def assert_equal_runs(ref, got):
    (outs_a, logits_a), (outs_b, logits_b) = ref, got
    for a, b in zip(outs_a, outs_b):
        assert a.token_ids == b.token_ids
        assert a.finish_reason == b.finish_reason
    for rid in logits_a:
        assert len(logits_a[rid]) == len(logits_b[rid])
        for la, lb in zip(logits_a[rid], logits_b[rid]):
            assert np.array_equal(la, lb), "logits must be bitwise-equal"


@pytest.fixture(scope="module")
def resident_reference(moe_model):
    return drive(make_llm(moe_model), PROMPTS, PLIST)


@pytest.mark.parametrize("cache", [8, 12, 16])
def test_tiered_bitwise_equal_across_cache_sizes(moe_model,
                                                 resident_reference, cache):
    """The acceptance contract: greedy and sampled rows through the tiered
    path match the resident path bitwise, at cache sizes from all-fit (16)
    down to heavy eviction churn (8 of 16 blocks)."""
    llm = make_llm(moe_model, expert_offchip=True, expert_cache_size=cache)
    engine = llm.cluster.replicas[0].engine
    ffn, slot_maps = engine._t_ffn, []

    def recording_ffn(*args):
        slot_maps.append(np.asarray(args[5]))
        return ffn(*args)

    engine._t_ffn = recording_ffn
    got = drive(llm, PROMPTS, PLIST)
    assert_equal_runs(resident_reference, got)
    # The grouped GEMM only ever names cache rows, even for experts that
    # only masked rows route to: on a TPU a -1 row is an out-of-bounds DMA.
    assert min(int(m.min()) for m in slot_maps) >= 0
    st = llm.engine.expert_store
    if cache < 16:
        assert st.evictions > 0, "sweep must actually churn the cache"


def test_lead_in_decode_bitwise_equal(moe_model):
    """Four rows x top-2 + 2 >= 8 experts: decode stages whole layers ahead
    with no forecast read, and still matches the resident path bitwise,
    at a cache that churns."""
    model, params = moe_model
    prompts = PROMPTS + [[4, 4, 2, 7, 1, 9]]
    plist = PLIST + [SamplingParams(max_tokens=6, temperature=0.7, seed=5)]

    def run(**kw):
        llm = LLM(model, params, ServeConfig(
            max_batch=4, page_size=4, hbm_pages=48, host_pages=64,
            max_pages_per_seq=16, interval_steps=4, keep_logits=True, **kw))
        return llm, drive(llm, prompts, plist)

    _, ref = run()
    llm, got = run(expert_offchip=True, expert_cache_size=8)
    assert_equal_runs(ref, got)
    stats = llm.engine.stats()
    assert stats["expert_evictions"] > 0
    assert 0 < stats["expert_landed_blocks"] \
        <= stats["expert_committed_blocks"]


def test_chunked_prefill_tight_cache_bitwise_equal(moe_model,
                                                   resident_reference):
    """At the decode floor (4 slots) a one-shot prefill working set cannot
    fit, but chunked prefill bounds each dispatch — and still matches the
    resident path bitwise."""
    llm = make_llm(moe_model, expert_offchip=True, expert_cache_size=4,
                   prefill_chunk_tokens=2)
    assert_equal_runs(resident_reference, drive(llm, PROMPTS, PLIST))
    assert llm.engine.expert_store.evictions > 0


def test_preemption_replay_through_tiered_path(moe_model):
    """Preemption-by-recompute must replay the identical stream when the
    re-prefill and resumed decode run through the expert cache."""
    def run(preempt):
        llm = make_llm(moe_model, expert_offchip=True, expert_cache_size=8)
        llm.submit(PROMPTS[0], SamplingParams(max_tokens=1)).result()
        h = llm.submit(PROMPTS[1], SamplingParams(
            max_tokens=8, temperature=0.9, seed=11))
        for _ in range(3):
            llm.step()
        if preempt:
            llm.pause(h.request_id)
            assert llm.engine._preempt_one(), "victim must exist"
            assert llm.engine.requests[h.request_id].state == "preempted"
            llm.resume(h.request_id)
        out = h.result()
        return out.token_ids, llm.engine.stats()

    calm, _ = run(preempt=False)
    replayed, stats = run(preempt=True)
    assert replayed == calm, \
        "preempted request must resample the identical stream"
    assert stats["preemptions"] >= 1


def test_one_shot_overflow_raises_named_error(moe_model):
    """A one-shot prefill whose distinct routed experts exceed the cache
    must raise the named error, not dispatch against wrong weights."""
    llm = make_llm(moe_model, expert_offchip=True, expert_cache_size=4)
    with pytest.raises(ExpertCacheMissError, match="expert_cache_size"):
        llm.submit(list(range(1, 13)), SamplingParams(max_tokens=2))
        for _ in range(4):
            llm.step()


def test_init_validation_names_knobs(moe_model):
    with pytest.raises(ValueError, match="expert_cache_size"):
        make_llm(moe_model, expert_offchip=True, expert_cache_size=2)
    with pytest.raises(ValueError, match="expert_cache_size"):
        make_llm(moe_model, expert_offchip=True, expert_cache_size=-1)


def test_offchip_requires_moe():
    cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="expert_offchip"):
        LLM(model, params, ServeConfig(
            max_batch=2, page_size=4, hbm_pages=16, host_pages=16,
            expert_offchip=True))


def test_serving_summary_reports_expert_counters(moe_model):
    llm = make_llm(moe_model, expert_offchip=True, expert_cache_size=8)
    drive(llm, PROMPTS, PLIST)
    stats = llm.engine.stats()
    assert stats["expert_cache_slots"] == 8
    assert stats["expert_demand_fetches"] > 0
    assert stats["expert_bytes_fetched"] > 0
    assert 0 <= stats["expert_resident_hits"] \
        <= stats["expert_needed_blocks"] > 0


def spy_pools(engine, names):
    """Wrap the engine programs ``names``: each call's pool tuple (argument
    1) is kept, by program and dispatch kind (None for the resident
    programs), for the test to inspect."""
    handed = {}
    for n in names:
        def call(*args, _fn=getattr(engine, n), _n=n, **kw):
            handed.setdefault((_n, kw.get("dispatch")), []).append(args[1])
            return _fn(*args, **kw)
        setattr(engine, n, call)
    return handed


@pytest.mark.parametrize("offchip, programs", [
    (False, (("_prefill", None), ("_decode_greedy", None),
             ("_decode_sampled", None))),
    (True, (("_t_attn", "prefill"), ("_t_attn", "decode"))),
], ids=["resident", "tiered"])
def test_programs_take_the_pools_donated(moe_model, offchip, programs):
    """Every program that returns the pools updated consumes the arrays it
    is handed (XLA writes them in place, never copies them whole), and the
    pool names live arrays after every step."""
    kw = dict(expert_offchip=True, expert_cache_size=8) if offchip else {}
    llm = make_llm(moe_model, **kw)
    engine = llm.cluster.replicas[0].engine
    handed = spy_pools(engine, {n for n, _ in programs})
    prompts = [[3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8]]
    params = [SamplingParams(max_tokens=6),
              SamplingParams(max_tokens=3, temperature=0.8, seed=7)]
    handles = [llm.submit(p, sp) for p, sp in zip(prompts, params)]
    while any(not h.finished for h in handles):
        llm.step()
        assert not any(a.is_deleted() for a in engine.pool.hbm)
    for program in programs:
        calls = handed.get(program)
        assert calls, f"{program} never ran"
        assert all(a.is_deleted() for pools in calls for a in pools), \
            f"{program} did not take the pools donated"
    for a in engine.pool.hbm:
        np.asarray(a)


def test_cache_miss_mid_step_leaves_the_pools_live(moe_model):
    """A prefill whose working set overflows the cache raises between the
    layer pieces, after a piece consumed the pools it was handed: the pool
    still names the arrays that piece returned, live and readable."""
    llm = make_llm(moe_model, expert_offchip=True, expert_cache_size=4)
    engine = llm.cluster.replicas[0].engine
    handed = spy_pools(engine, ("_t_attn",))
    with pytest.raises(ExpertCacheMissError, match="expert_cache_size"):
        llm.submit(list(range(1, 13)), SamplingParams(max_tokens=2))
        for _ in range(4):
            llm.step()
    assert all(a.is_deleted() for a in handed["_t_attn", "prefill"][0])
    pools = engine.pool.hbm
    assert not any(a.is_deleted() for a in pools)
    for a in pools:
        assert np.isfinite(np.asarray(a)).all()
