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


def make_store(cache_slots, double_buffer=False):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    moe_params = {
        "w_gate": jax.random.normal(ks[0], (L, E, D, F), jnp.float32),
        "w_up": jax.random.normal(ks[1], (L, E, D, F), jnp.float32),
        "w_down": jax.random.normal(ks[2], (L, E, F, D), jnp.float32),
    }
    return moe_params, ExpertStore(moe_params, L, E, cache_slots,
                                   double_buffer=double_buffer)


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
    _, st = make_store(4, double_buffer=True)
    st.dispatch(0, counts(0, 1), step=1)
    assert st.prefetch(1, step=1, predicted=[0, 1]) == 2
    st.dispatch(1, counts(0, 2), step=1)
    assert st.prefetch_fetches == 2
    assert st.prefetch_hits == 1               # predicted 0, routed {0, 2}
    assert st.demand_fetches == 3              # (0,0) (0,1) + fallback (1,2)


def test_prefetch_never_evicts_pins_or_its_own_forecast():
    _, st = make_store(2, double_buffer=True)
    st.dispatch(0, counts(0, 1), step=1)       # both slots pinned
    assert st.prefetch(1, step=1, predicted=[2, 3]) == 0
    assert st.dropped_prefetches == 2
    assert st.is_resident(0, 0) and st.is_resident(0, 1)


def test_prefetch_disabled_in_sync_mode():
    _, st = make_store(4, double_buffer=False)
    st.dispatch(0, counts(0), step=1)
    assert st.prefetch(1, step=1, predicted=[0, 1]) == 0
    assert st.prefetch_fetches == 0


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


def test_double_buffer_equals_sync(moe_model, resident_reference):
    """Prefetch is pure staging: a misprediction falls back to the demand
    fetch, so db on/off both equal the resident reference bitwise."""
    for db in (True, False):
        llm = make_llm(moe_model, expert_offchip=True, expert_cache_size=8,
                       expert_double_buffer=db)
        assert_equal_runs(resident_reference, drive(llm, PROMPTS, PLIST))


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
