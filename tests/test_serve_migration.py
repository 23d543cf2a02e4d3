"""Batched tier-migration parity: a whole ``MigrationPlan`` direction is one
gather + one staged transfer + one scatter per pool array.  The batched path
must produce identical pool contents and counters to the per-page path, with
a constant number of host<->device transfers per direction — and a migration
storm must leave decode bitwise unchanged."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.runtime import MigrationPlan
from repro.models import build_model
from repro.serve import Engine, PagedKVBackend, ServeConfig
from repro.serve.kvcache import PagedKVPool


def make_pool(seed=0):
    """Pool with 6 allocated pages (2 requests), recognizable K/V contents,
    and two pages pre-spilled to the host tier."""
    pool = PagedKVPool(n_layers=2, page_size=4, kv_heads=2, head_dim=8,
                       hbm_pages=8, host_pages=16, dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    pool.hbm = tuple(jnp.asarray(rng.normal(size=a.shape), jnp.float32)
                     for a in pool.hbm)
    for rid in (0, 1):
        for idx in range(3):
            pool.allocate(rid, idx, step=0)
    # Spill one page of each request so the plan has promotions to do.
    pool.swap_out(pool.request_pages(0)[2].page_id)
    pool.swap_out(pool.request_pages(1)[0].page_id)
    return pool


def page_state(pool):
    return sorted((pid, p.hbm_slot, p.host_slot)
                  for pid, p in pool.pages.items())


def pool_bits(pool):
    """HBM pools whole, the host tier slot by slot."""
    slots = sorted(s for s in range(pool.host_pages) if s in pool.host)
    k_rows, v_rows = pool.host.read(slots) if slots else ([], [])
    host = tuple((s, k.tobytes(), v.tobytes())
                 for s, k, v in zip(slots, k_rows, v_rows))
    return tuple(np.asarray(a).tobytes() for a in pool.hbm) + (host,)


def make_plan(placement):
    """MigrationPlan stub: ``enforce`` only reads ``chunk_placement``."""
    return MigrationPlan(
        profile=None, exploded=None, fragments=[], assignment=None,
        decision=None, fractions={}, chunk_placement=placement,
        capacity_bytes=0, strategy="thermos")


def test_batched_enforce_matches_per_page_path():
    placement = None
    results = {}
    for path in ("batched", "per_page"):
        pool = make_pool()
        backend = PagedKVBackend(pool, {0: object(), 1: object()},
                                 clock=lambda: 0)
        if placement is None:
            # Demote the two hot pages of request 0 still in HBM; promote
            # both spilled pages.  Same dict order for both paths.
            r0, r1 = pool.request_pages(0), pool.request_pages(1)
            placement = {r0[0].page_id: False, r0[1].page_id: False,
                         r0[2].page_id: True, r1[0].page_id: True}
        t0 = pool.transfer_events
        if path == "batched":
            stats = backend.enforce(make_plan(placement))
            assert stats.bytes_demoted == 2 * pool.page_bytes
            assert stats.bytes_promoted == 2 * pool.page_bytes
            assert stats.dropped_promotions == 0
            # Constant transfers per direction: K+V for demote, K+V for
            # promote — not 2 per page.
            assert pool.transfer_events - t0 == 4
        else:
            for pid, fast in placement.items():
                if not fast:
                    pool.swap_out(pid)
            for pid, fast in placement.items():
                if fast:
                    pool.swap_in(pid)
            assert pool.transfer_events - t0 == 2 * len(placement)
        results[path] = (page_state(pool), pool_bits(pool),
                         pool.swaps_in, pool.swaps_out, pool.bytes_moved)
    assert results["batched"] == results["per_page"], \
        "batched migration must be observationally identical to per-page"


def test_batched_roundtrip_preserves_contents():
    """N pages out and back in one batch each way: contents bit-identical,
    counters exact, 2 transfers per direction."""
    pool = make_pool(seed=7)
    resident = [p.page_id for p in pool.pages.values()
                if p.hbm_slot is not None]
    before = {pid: np.asarray(pool.hbm[0][:, pool.pages[pid].hbm_slot])
              for pid in resident}
    t0, s_in, s_out = pool.transfer_events, pool.swaps_in, pool.swaps_out
    pool.swap_out_many(resident)
    assert all(pool.pages[pid].hbm_slot is None for pid in resident)
    pool.swap_in_many(resident)
    assert pool.transfer_events - t0 == 4
    assert pool.swaps_out - s_out == len(resident)
    assert pool.swaps_in - s_in == len(resident)
    for pid in resident:
        after = np.asarray(pool.hbm[0][:, pool.pages[pid].hbm_slot])
        assert np.array_equal(before[pid], after)


def test_batched_migration_empty_lists_are_noops():
    """Empty id lists must not touch pools, counters or transfer probes —
    the prefix backend routinely enforces plans with nothing to move."""
    pool = make_pool()
    before = (page_state(pool), pool_bits(pool), pool.swaps_in,
              pool.swaps_out, pool.bytes_moved, pool.transfer_events,
              list(pool.free_hbm), list(pool.free_host))
    pool.swap_out_many([])
    pool.swap_in_many([])
    pool.exchange([], [])
    after = (page_state(pool), pool_bits(pool), pool.swaps_in,
             pool.swaps_out, pool.bytes_moved, pool.transfer_events,
             list(pool.free_hbm), list(pool.free_host))
    assert before == after


def test_batched_migration_duplicate_ids_move_once():
    """Duplicate ids in one batch must behave exactly like the deduplicated
    batch (a duplicate that moved twice would corrupt the free lists)."""
    results = {}
    for dup in (False, True):
        pool = make_pool(seed=3)
        r0 = pool.request_pages(0)
        out_ids = [r0[0].page_id, r0[1].page_id]
        in_ids = [r0[2].page_id]
        if dup:
            out_ids = out_ids + out_ids[:1] * 3
            in_ids = in_ids * 2
        pool.exchange(out_ids, in_ids)
        pool.swap_in_many(out_ids + out_ids)      # duplicates again
        pool.swap_out_many(in_ids + in_ids)
        results[dup] = (page_state(pool), pool_bits(pool), pool.swaps_in,
                        pool.swaps_out, pool.bytes_moved,
                        sorted(pool.free_hbm), sorted(pool.free_host))
    assert results[True] == results[False], \
        "duplicate ids must migrate once, identically to the deduped batch"


def test_batched_migration_refcounted_pages_parity():
    """Pages shared by multiple requests (refcount > 1) migrate exactly
    like single-owner pages: one physical move, every holder's page list
    sees the same slot, batched == per-page."""
    results = {}
    for path in ("batched", "per_page"):
        pool = make_pool(seed=11)
        # Request 2 shares request 0's leading resident pages.
        shared = [p for p in pool.request_pages(0) if p.hbm_slot is not None]
        for p in shared:
            pool.attach(2, p.page_id, step=1)
        ids = [p.page_id for p in shared]
        if path == "batched":
            pool.swap_out_many(ids)
            pool.swap_in_many(ids)
        else:
            for pid in ids:
                pool.swap_out(pid)
            for pid in ids:
                pool.swap_in(pid)
        assert all(p.refcount == 2 for p in shared)
        assert [p.page_id for p in pool.request_pages(2)] == ids
        results[path] = (page_state(pool), pool_bits(pool), pool.swaps_in,
                         pool.swaps_out, pool.bytes_moved)
    assert results["batched"] == results["per_page"]


def test_migration_storm_leaves_decode_unchanged():
    """Engine-level: forcing whole-pool round-trip migrations between steps
    must not change a single generated token."""
    cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = [5, 17, 133, 42, 7, 99, 250, 3]

    def run(storm):
        eng = Engine(model, params,
                     ServeConfig(max_batch=1, page_size=4, hbm_pages=16,
                                 host_pages=32, policy="gdt"))
        eng.add_request(0, prompt, max_new=6)
        while 0 in eng.requests:
            if storm:
                ids = [p.page_id for p in eng.pool.request_pages(0)]
                eng.pool.swap_out_many(ids)
                eng.pool.swap_in_many(ids)
            eng.step()
        return eng.finished[0].generated, eng.pool.swaps_out

    calm, _ = run(storm=False)
    stormy, swaps = run(storm=True)
    assert swaps > 0
    assert stormy == calm, "migration storm changed decode output"


def test_host_tier_roundtrip_keeps_memory_kinds_and_bits():
    """HBM -> pinned_host -> HBM: swapped pages land in pinned_host slabs,
    the HBM pool stays on the device kind, and the bytes come back
    bitwise."""
    pool = make_pool(seed=11)
    ids = [p.page_id for p in pool.pages.values() if p.hbm_slot is not None]
    before = {pid: tuple(np.asarray(a[:, pool.pages[pid].hbm_slot])
                         for a in pool.hbm)
              for pid in ids}
    pool.swap_out_many(ids)
    assert pool.host.memory_kinds() == {"pinned_host"}
    for pid in ids:
        (k,), (v,) = pool.host.read([pool.pages[pid].host_slot])
        assert np.array_equal(k, before[pid][0])
        assert np.array_equal(v, before[pid][1])
    pool.swap_in_many(ids)
    assert [a.sharding.memory_kind for a in pool.hbm] == ["device"] * 2
    for pid in ids:
        slot = pool.pages[pid].hbm_slot
        k, v = (np.asarray(a[:, slot]) for a in pool.hbm)
        assert np.array_equal(k, before[pid][0])
        assert np.array_equal(v, before[pid][1])
    held = {p.host_slot for p in pool.pages.values()} - {None}
    assert {s for s in range(pool.host_pages) if s in pool.host} == held, \
        "a freed host slot must leave the host tier"
