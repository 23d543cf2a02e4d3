"""Cross-request radix prefix cache: pool refcount lifecycle (named free /
allocate errors), radix match/insert/reclaim semantics, copy-on-write, the
cached-vs-uncached bitwise-equality contract through ``LLM.generate``
(greedy and sampled, including across preemption-by-recompute), full-hit
prefill skipping, and guided tier placement of shared prefixes through
``PrefixBackend``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.runtime import MigrationPlan
from repro.models import build_model
from repro.serve import LLM, SamplingParams, ServeConfig
from repro.serve.kvcache import PagedKVPool
from repro.serve.prefix_cache import PrefixBackend, PrefixCache, block_hash


# ============================================================ pool fixtures
def small_pool(hbm=8, host=16):
    return PagedKVPool(n_layers=2, page_size=4, kv_heads=2, head_dim=8,
                       hbm_pages=hbm, host_pages=host, dtype=jnp.float32)


def full_pages(pool, rid, n, step=0):
    """Allocate ``n`` FULL pages (the only shareable kind) for ``rid``."""
    pages = [pool.allocate(rid, i, step) for i in range(n)]
    for p in pages:
        p.tokens_used = pool.page_size
    return pages


# ===================================================== satellite: free()
def test_free_unknown_id_raises_named_error():
    pool = small_pool()
    with pytest.raises(ValueError, match="unknown or already-freed"):
        pool.free(999)


def test_double_free_raises_named_error():
    pool = small_pool()
    page = pool.allocate(0, 0, step=0)
    pool.free(page.page_id)
    with pytest.raises(ValueError, match="refcount reaches zero"):
        pool.free(page.page_id)


def test_free_is_refcount_decrement():
    pool = small_pool()
    page = pool.allocate(0, 0, step=0)
    pool.acquire(page.page_id, shared=True)
    free_before = len(pool.free_hbm)
    pool.free(page.page_id)                 # cache ref survives
    assert page.page_id in pool.pages
    assert len(pool.free_hbm) == free_before
    pool.free(page.page_id)                 # last ref: slot returns
    assert page.page_id not in pool.pages
    assert len(pool.free_hbm) == free_before + 1


# ================================================= satellite: allocate()
def test_allocate_exhausted_names_knob():
    pool = small_pool(hbm=2)
    full_pages(pool, 0, 2)
    with pytest.raises(MemoryError, match="ServeConfig.hbm_pages"):
        pool.allocate(0, 2, step=0)


# ===================================================== refcount lifecycle
def test_release_request_returns_only_dead_pages():
    pool = small_pool()
    pages = full_pages(pool, 0, 3)
    pool.acquire(pages[0].page_id, shared=True)   # cache holds page 0
    dead = pool.release_request(0)
    assert dead == [pages[1].page_id, pages[2].page_id]
    assert pages[0].page_id in pool.pages
    assert pool.request_pages(0) == []


def test_attach_enforces_prefix_order():
    pool = small_pool()
    pages = full_pages(pool, 0, 2)
    with pytest.raises(ValueError, match="attach in order"):
        pool.attach(1, pages[1].page_id, step=0)   # index 1 before 0
    pool.attach(1, pages[0].page_id, step=0)
    pool.attach(1, pages[1].page_id, step=0)
    assert [p.page_id for p in pool.request_pages(1)] == \
        [p.page_id for p in pages]
    assert pool.holders(pages[0].page_id) == [0, 1]


def test_copy_page_gives_private_bitwise_copy():
    pool = small_pool()
    rng = np.random.default_rng(0)
    pool.hbm = tuple(jnp.asarray(rng.normal(size=a.shape), jnp.float32)
                     for a in pool.hbm)
    (src,) = full_pages(pool, 0, 1)
    pool.attach(1, src.page_id, step=0)
    before_k = np.asarray(pool.hbm[0][:, src.hbm_slot])
    new = pool.copy_page(src.page_id, 1, step=1)
    assert new.page_id != src.page_id
    assert src.refcount == 1                     # writer's ref moved over
    assert pool.request_pages(1) == [new]
    assert pool.request_pages(0) == [src]
    assert np.array_equal(np.asarray(pool.hbm[0][:, new.hbm_slot]), before_k)


# ========================================================== radix cache
def test_block_hash_commits_to_left_context():
    a = block_hash(b"", (1, 2, 3, 4))
    b = block_hash(a, (5, 6, 7, 8))
    c = block_hash(block_hash(b"", (9, 2, 3, 4)), (5, 6, 7, 8))
    assert a != b and b != c                    # same block, different chain


def test_match_insert_roundtrip_and_min_pages_gate():
    pool = small_pool()
    cache = PrefixCache(pool, page_size=4, min_pages=2)
    tokens = list(range(1, 13))                 # 3 full pages
    pages = full_pages(pool, 0, 3)
    # Below the gate: a 1-page prefix must not enter.
    assert cache.insert(tokens[:4], pages[:1], limit=4, step=0) == 0
    assert len(cache) == 0
    assert cache.insert(tokens, pages, limit=12, step=0) == 3
    assert len(cache) == 3
    chain = cache.match(tokens + [99], step=1)
    assert [n.page_id for n in chain] == [p.page_id for p in pages]
    assert cache.match(tokens[:8], step=1) and cache.hit_pages == 5
    # Diverging block: no match past the shared prefix.
    assert [n.depth for n in cache.match([7] * 12, step=2)] == []
    assert cache.hit_rate == pytest.approx(2 / 3)


def test_reclaim_drops_coldest_leaf_and_cascades():
    pool = small_pool()
    cache = PrefixCache(pool, page_size=4)
    tokens = list(range(1, 13))
    pages = full_pages(pool, 0, 3)
    cache.insert(tokens, pages, limit=12, step=0)
    pool.release_request(0)                     # cache-only references now
    # A live holder pins its chain: attach a request to the first page.
    pool.attach(1, pages[0].page_id, step=1)
    # Only the childless leaf (depth 2) is evictable; reclaiming 3 pages
    # cascades leaf-by-leaf but must stop at the pinned root.
    assert {n.depth for n in cache.evictable()} == {2}
    assert cache.reclaim(3) == 2
    assert len(cache) == 1 and cache.evicted_pages == 2
    assert pages[0].page_id in pool.pages
    assert cache.reclaim(1) == 0                # pinned by request 1


# ============================================== engine-level equivalence
@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def make_llm(model_and_params, **kw):
    model, params = model_and_params
    return LLM(model, params, ServeConfig(
        max_batch=4, page_size=4, hbm_pages=32, host_pages=64,
        max_pages_per_seq=16, interval_steps=4, keep_logits=True, **kw))


SHARED = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]    # 3 full pages


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


def test_cached_vs_uncached_bitwise_equal(model_and_params):
    """The acceptance contract: identical prompts through ``LLM.generate``
    with and without the prefix cache produce bitwise-equal logits and
    sampled token streams — greedy and temperature>0 rows both."""
    prompts = [SHARED + [20 + i] for i in range(3)] + [SHARED[:9]]
    plist = [SamplingParams(max_tokens=5),
             SamplingParams(max_tokens=5, temperature=0.8, top_k=40,
                            seed=7),
             SamplingParams(max_tokens=5, temperature=1.1, top_p=0.9),
             SamplingParams(max_tokens=5)]
    outs_off, logits_off = drive(make_llm(model_and_params), prompts, plist)
    llm = make_llm(model_and_params, enable_prefix_cache=True)
    outs_on, logits_on = drive(llm, prompts, plist)
    for a, b in zip(outs_off, outs_on):
        assert a.token_ids == b.token_ids
        assert a.finish_reason == b.finish_reason
    for rid in logits_off:
        assert len(logits_off[rid]) == len(logits_on[rid])
        for la, lb in zip(logits_off[rid], logits_on[rid]):
            assert np.array_equal(la, lb), "logits must be bitwise-equal"
    stats = llm.engine.stats()
    assert stats["prefix_hit_requests"] >= 2
    assert stats["saved_prefill_tokens"] >= 24


def test_full_hit_skips_prefill_dispatch(model_and_params):
    """A repeat of a prompt whose whole ingested span is cached must not
    dispatch prefill at all."""
    llm = make_llm(model_and_params, enable_prefix_cache=True)
    prompt = SHARED + [42]                       # n_ingest = 12 = 3 pages
    sp = SamplingParams(max_tokens=3)
    first = llm.submit(prompt, sp).result()
    d0 = llm.engine.prefill_dispatches
    second = llm.submit(prompt, sp).result()
    assert llm.engine.prefill_dispatches == d0, \
        "full prefix hit must skip the prefill dispatch"
    assert second.token_ids == first.token_ids   # same rid-independent path?
    stats = llm.engine.stats()
    assert stats["prefix_hit_requests"] >= 1
    assert stats["saved_prefill_tokens"] >= 12


def test_preemption_replay_through_cache_hit(model_and_params):
    """Preemption-by-recompute must replay identically when the re-prefill
    is served (partly) from the prefix cache."""
    def run(preempt):
        llm = make_llm(model_and_params, enable_prefix_cache=True)
        llm.submit(SHARED + [77], SamplingParams(max_tokens=1)).result()
        h = llm.submit(SHARED + [88],
                       SamplingParams(max_tokens=8, temperature=0.9,
                                      seed=11))
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
        "preempted request must resample the identical stream via the cache"
    assert stats["preemptions"] >= 1
    assert stats["prefix_hit_requests"] >= 2     # admit + re-admit both hit


def test_chunked_prefill_equals_one_shot_through_cache(model_and_params):
    """The chunked oracle must agree with one-shot when both run their
    suffix behind the same cache hit."""
    outs = {}
    for mode in ("one_shot", "chunked"):
        llm = make_llm(model_and_params, enable_prefix_cache=True,
                       prefill=mode)
        llm.submit(SHARED + [50], SamplingParams(max_tokens=1)).result()
        outs[mode] = llm.submit(
            SHARED + [51, 52, 53],
            SamplingParams(max_tokens=4)).result().token_ids
        assert llm.engine.stats()["prefix_hit_requests"] >= 1
    assert outs["one_shot"] == outs["chunked"]


# ====================================================== guided placement
def make_plan(placement):
    return MigrationPlan(
        profile=None, exploded=None, fragments=[], assignment=None,
        decision=None, fractions={}, chunk_placement=placement,
        capacity_bytes=0, strategy="thermos")


def seeded_cache():
    pool = small_pool(hbm=6, host=8)
    cache = PrefixCache(pool, page_size=4)
    tokens = list(range(1, 13))
    pages = full_pages(pool, 0, 3)
    cache.insert(tokens, pages, limit=12, step=0)
    pool.release_request(0)
    return pool, cache, tokens, pages


def test_prefix_backend_enforce_demotes_and_promotes():
    pool, cache, tokens, pages = seeded_cache()
    backend = PrefixBackend(cache, clock=lambda: 0)
    ids = [p.page_id for p in pages]
    backend.enforce(make_plan({pid: False for pid in ids}))
    assert all(pool.pages[pid].hbm_slot is None for pid in ids)
    stats = backend.enforce(make_plan({pid: True for pid in ids}))
    assert all(pool.pages[pid].hbm_slot is not None for pid in ids)
    assert stats.bytes_promoted == 3 * pool.page_bytes
    # Hits on the promoted chain keep flowing into the access profile.
    cache.match(tokens, step=1)
    snap = backend.snapshot()
    assert len(snap.rows) == 1
    assert snap.rows[0].accesses == pytest.approx(3.0)


def test_prefix_backend_never_demotes_referenced_pages():
    pool, cache, tokens, pages = seeded_cache()
    backend = PrefixBackend(cache, clock=lambda: 0)
    chain = cache.match(tokens, step=1)
    for node in chain[:2]:                       # a live request holds 0, 1
        pool.attach(5, node.page_id, step=1)
    backend.enforce(make_plan({p.page_id: False for p in pages}))
    assert pool.pages[pages[0].page_id].hbm_slot is not None
    assert pool.pages[pages[1].page_id].hbm_slot is not None
    assert pool.pages[pages[2].page_id].hbm_slot is None


def test_prefix_runtime_drives_interval_loop(model_and_params):
    """End-to-end: under the guided policy the SECOND controller (shared
    prefixes as tier objects) emits interval events and its plans reach
    ``engine.last_recs``."""
    llm = make_llm(model_and_params, enable_prefix_cache=True)
    eng = llm.engine
    assert eng.prefix_runtime is not None
    sp = SamplingParams(max_tokens=6)
    llm.generate([SHARED + [60 + i] for i in range(3)], sp)
    intervals = [e for e in eng.prefix_runtime.events
                 if getattr(e, "kind", None) == "interval"]
    assert intervals, "prefix controller must run at the decision interval"
    cached = set(eng.prefix_cache.by_page)
    assert cached & set(eng.last_recs), \
        "prefix placements must reach the merged eviction view"
