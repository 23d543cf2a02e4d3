"""Serving engine tests: paged decode correctness (vs the contiguous-cache
model decode), one-shot vs chunked prefill equality, two-tier page
migration, partial-batch masking, and the guided-policy benefit."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import build_model
from repro.serve import Engine, ServeConfig


@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def moe_model_and_params():
    cfg = dataclasses.replace(get_smoke("granite_moe_3b_a800m"), remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def greedy_reference(model, params, prompt, n_new, cache_len=64):
    """Contiguous-cache greedy decode (the model's own serve path)."""
    cache = model.init_cache(1, cache_len)
    toks = list(prompt)
    logits = None
    for t, tok in enumerate(toks):
        logits, cache = jax.jit(model.decode)(
            params, cache, jnp.asarray([[tok]], jnp.int32), jnp.int32(t))
    out = []
    pos = len(toks)
    for _ in range(n_new):
        nxt = int(jnp.argmax(logits[0]))
        out.append(nxt)
        logits, cache = jax.jit(model.decode)(
            params, cache, jnp.asarray([[nxt]], jnp.int32), jnp.int32(pos))
        pos += 1
    return out


def still_live(eng, rid):
    """Finished requests are pruned from ``engine.requests``."""
    return rid in eng.requests


def generated(eng, rid):
    req = eng.finished.get(rid) or eng.requests.get(rid)
    return req.generated


def request_pages_bits(eng, rid):
    """K/V page contents for one request, in logical page order."""
    out = []
    for p in eng.pool.request_pages(rid):
        assert p.hbm_slot is not None
        out.extend(np.asarray(a[:, p.hbm_slot]) for a in eng.pool.hbm)
    return out


def test_paged_decode_matches_contiguous(model_and_params):
    model, params = model_and_params
    prompt = [5, 17, 133, 42, 7, 99, 250, 3]
    n_new = 6
    ref = greedy_reference(model, params, prompt, n_new)

    eng = Engine(model, params,
                 ServeConfig(max_batch=2, page_size=4, hbm_pages=32,
                             host_pages=64, policy="gdt", interval_steps=4))
    eng.add_request(0, prompt, max_new=n_new)
    got = []
    while still_live(eng, 0):
        out = eng.step()
        if 0 in out:
            got.append(out[0])
    assert got == ref, f"paged {got} != contiguous {ref}"


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_one_shot_prefill_bitwise_equals_chunked(
        family, model_and_params, moe_model_and_params):
    """The tentpole equality: a whole prompt ingested in ONE jitted dispatch
    must produce bitwise the same K/V pages and the same continuation as
    stepping the prompt through decode token by token (which in turn is the
    decode path itself) — on dense and MoE smoke configs."""
    model, params = (model_and_params if family == "dense"
                     else moe_model_and_params)
    prompt = [5, 17, 133, 42, 7, 99, 250, 3, 11, 29]
    n_new = 5

    def make(mode):
        eng = Engine(model, params,
                     ServeConfig(max_batch=2, page_size=4, hbm_pages=32,
                                 host_pages=64, policy="gdt",
                                 interval_steps=4, prefill=mode))
        eng.add_request(0, prompt, max_new=n_new)
        return eng

    one, chunked = make("one_shot"), make("chunked")
    # O(1) jitted dispatches for an S-token prompt, not S.
    assert one.prefill_dispatches == 1
    assert chunked.prefill_dispatches == len(prompt) - 1
    for a, b in zip(request_pages_bits(one, 0), request_pages_bits(chunked, 0)):
        assert np.array_equal(a, b), "prefill K/V pages differ bitwise"
    while still_live(one, 0):
        one.step()
    while still_live(chunked, 0):
        chunked.step()
    assert generated(one, 0) == generated(chunked, 0)


def test_eager_and_interleaved_ingest_agree(model_and_params):
    """Eager prefill is interleaved prefill in one chunk: for one prompt,
    eager ingestion and interleaved ingestion under a budget of at least
    the prompt leave the same page table, per-page accesses and
    tokens_used, K/V bits and continuation, and the same stats apart from
    the count of interleaved chunks."""
    model, params = model_and_params
    prompt = [(5 * j) % 50 + 1 for j in range(23)]

    def run(chunk):
        eng = Engine(model, params,
                     ServeConfig(max_batch=2, page_size=4, hbm_pages=32,
                                 host_pages=32, enable_prefix_cache=True,
                                 prefill_chunk_tokens=chunk))
        eng.add_request(0, prompt, max_new=6)
        for _ in range(2):
            eng.step()
        pages = [(p.index_in_seq, p.hbm_slot, p.accesses, p.tokens_used)
                 for p in eng.pool.request_pages(0)]
        return (pages, request_pages_bits(eng, 0), eng.stats(),
                generated(eng, 0))

    eager, interleaved = run(0), run(len(prompt))
    assert eager[0] == interleaved[0]
    for a, b in zip(eager[1], interleaved[1]):
        assert np.array_equal(a, b), "prefill K/V pages differ bitwise"
    assert eager[3] == interleaved[3]
    assert eager[2].pop("prefill_chunks") == 0
    assert interleaved[2].pop("prefill_chunks") == 1
    assert eager[2] == interleaved[2]


def test_partial_batch_logits_match_full_batch(model_and_params):
    """Inactive batch rows are explicitly masked: a partial batch (2 live
    requests in a 4-slot batch) must produce bitwise the same logits for
    those requests as a full batch that also carries two more."""
    model, params = model_and_params
    prompts = {0: [5, 17, 133, 42], 1: [7, 99, 250, 3],
               2: [11, 29, 31, 2], 3: [1, 2, 3, 4]}

    def make(rids):
        eng = Engine(model, params,
                     ServeConfig(max_batch=4, page_size=4, hbm_pages=48,
                                 host_pages=64, policy="gdt",
                                 keep_logits=True))
        for rid in rids:
            eng.add_request(rid, prompts[rid], max_new=4)
        return eng

    partial, full = make([0, 1]), make([0, 1, 2, 3])
    for _ in range(4):
        partial.step()
        full.step()
        for rid in (0, 1):
            if rid in partial.last_logits and rid in full.last_logits:
                assert np.array_equal(partial.last_logits[rid],
                                      full.last_logits[rid]), \
                    f"rid {rid}: partial-batch logits != full-batch"
    assert generated(partial, 0) == generated(full, 0)
    assert generated(partial, 1) == generated(full, 1)


def test_multiple_concurrent_requests(model_and_params):
    model, params = model_and_params
    eng = Engine(model, params,
                 ServeConfig(max_batch=4, page_size=4, hbm_pages=48,
                             host_pages=96))
    for rid in range(6):
        eng.add_request(rid, [1 + rid, 2 + rid, 3 + rid], max_new=5)
    for _ in range(30):
        eng.step()
        if not eng.requests:
            break
    assert not eng.requests, "finished requests must leave the engine"
    assert len(eng.finished) == 6
    assert all(len(r.generated) == 5 for r in eng.finished.values())


def test_pages_migrate_under_pressure(model_and_params):
    """More session state than HBM pages: pages must spill to the host tier
    and come back correctly when sessions resume."""
    model, params = model_and_params
    eng = Engine(model, params,
                 ServeConfig(max_batch=1, page_size=4, hbm_pages=10,
                             host_pages=64, policy="gdt", interval_steps=2))
    # 12-token prompts -> 3 pages per session; three paused sessions fill
    # all 9 usable HBM pages, so the active one must force evictions.
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
    ref = greedy_reference(model, params, prompt, 4)

    # Fill HBM with paused sessions.
    for rid in range(3):
        eng.add_request(rid, prompt, max_new=4)
        eng.pause(rid)
    # New active session forces evictions.
    eng.add_request(99, prompt, max_new=4)
    got99 = []
    while still_live(eng, 99):
        out = eng.step()
        if 99 in out:
            got99.append(out[99])
    assert got99 == ref
    assert eng.pool.swaps_out > 0, "nothing ever spilled"

    # Resume a paused session: its pages swap back in and it decodes the
    # exact same continuation.
    eng.resume(0)
    got0 = []
    while still_live(eng, 0):
        out = eng.step()
        if 0 in out:
            got0.append(out[0])
    assert got0 == ref
    assert eng.pool.swaps_in > 0


def test_reweight_keeps_float_counters_and_ordering(model_and_params):
    """ReweightProfile must not floor counters to int: at access_decay=0.5 a
    page with one access per interval would be zeroed, erasing exactly the
    recency ordering decay is meant to preserve."""
    model, params = model_and_params
    eng = Engine(model, params,
                 ServeConfig(max_batch=1, page_size=4, hbm_pages=16,
                             host_pages=32, policy="gdt"))
    eng.add_request(0, [1, 2, 3, 4, 5, 6, 7, 8], max_new=4)
    pages = eng.pool.request_pages(0)
    pages[0].accesses = 1.0      # cold-ish page
    pages[1].accesses = 3.0      # hot page
    backend = eng.kv_backend
    backend.reweight(0.5)
    backend.reweight(0.5)
    assert pages[0].accesses == pytest.approx(0.25)
    assert pages[1].accesses == pytest.approx(0.75)
    assert 0 < pages[0].accesses < pages[1].accesses, \
        "two decay intervals must preserve relative page ordering"


def run_session_workload(model, params, policy, seed=0):
    """Sessions pause/resume; hot sessions resume often.  Returns stats."""
    rng = np.random.default_rng(seed)
    eng = Engine(model, params,
                 ServeConfig(max_batch=2, page_size=4, hbm_pages=12,
                             host_pages=128, policy=policy,
                             interval_steps=4))
    prompt = [2, 7, 1, 8, 2, 8]
    # Two hot sessions, four cold ones.
    for rid in range(6):
        eng.add_request(rid, prompt, max_new=24)
        eng.pause(rid)
    hot = [0, 1]
    for round_ in range(12):
        # hot sessions resume every round, cold ones rarely
        for rid in hot:
            eng.resume(rid)
        if round_ % 5 == 4:
            eng.resume(2 + (round_ // 5) % 4)
        for _ in range(2):
            eng.step()
        for rid in list(eng.requests):
            if eng.requests[rid].state == "active":
                eng.pause(rid)
    return eng.stats()


@pytest.mark.parametrize("policy", ["gdt", "lru"])
def test_bulk_victim_ranking_equals_repeated_pick(model_and_params, policy):
    """``pick_many`` ranks once; it must name the same victims, in the same
    order, as picking one page at a time (the base-class definition)."""
    from repro.serve.eviction import EvictionPolicy, make_eviction_policy

    model, params = model_and_params
    eng = Engine(model, params,
                 ServeConfig(max_batch=2, page_size=4, hbm_pages=24,
                             host_pages=64, policy=policy, interval_steps=2))
    for rid in range(4):
        eng.add_request(rid, [2, 7, 1, 8, 2, 8, 1, 8][:5 + rid], max_new=12)
        eng.pause(rid)
    for rid in (0, 2):
        eng.resume(rid)
    for _ in range(5):
        eng.step()
    cands = [p for p in eng.pool.pages.values() if p.hbm_slot is not None]
    policy_obj = make_eviction_policy(policy)
    for n in (1, 3, len(cands)):
        assert policy_obj.pick_many(cands, eng, n) == \
            EvictionPolicy.pick_many(policy_obj, cands, eng, n)


def test_gdt_policy_beats_fifo_on_sessions(model_and_params):
    model, params = model_and_params
    s_gdt = run_session_workload(model, params, "gdt")
    s_fifo = run_session_workload(model, params, "fifo")
    # Guided placement keeps hot sessions' pages resident -> fewer swap-ins.
    assert s_gdt["swap_ins"] <= s_fifo["swap_ins"]
    assert s_gdt["bytes_moved"] <= s_fifo["bytes_moved"]


def test_controller_tick_order_is_pinned(moe_model_and_params):
    """``_tick_controllers`` runs every guidance controller once per step
    in a FIXED order — KV pages, shared prefixes, expert weights.  The
    order is part of the replay contract (it decides which controller
    sees the interval's free HBM first), so a reorder must fail here."""
    model, params = moe_model_and_params
    eng = Engine(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=24, host_pages=64,
        policy="gdt", interval_steps=4, enable_prefix_cache=True,
        expert_offchip=True, expert_cache_size=8))
    ticked = []
    for name, rt in (("paged_kv", eng.runtime),
                     ("prefix", eng.prefix_runtime),
                     ("expert", eng.expert_runtime)):
        assert rt is not None, f"{name} controller must exist in this cfg"

        def record(orig=rt.on_step, name=name):
            ticked.append(name)
            return orig()

        rt.on_step = record
    eng.add_request(0, [3, 1, 4, 1, 5, 9], max_new=6)
    n_steps = 0
    while eng.requests:
        eng.step()
        n_steps += 1
    assert n_steps >= 3
    assert ticked == ["paged_kv", "prefix", "expert"] * n_steps
