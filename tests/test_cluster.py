"""Elastic multi-replica serving tests: router dispatch, replica
lifecycle (fail/drain/remove/restart), live KV migration (cold by
recompute, warm by page export/import), and the loud-loss contract
(``ReplicaLostError``).

The determinism spine: a resumed stream after a mid-decode replica kill
must be bitwise-equal to the unkilled run — on the cold path because
preemption-by-recompute replays (prompt, seed, position) exactly, on the
warm path because ``export_pages``/``import_pages`` move the literal KV
bytes.  One reference run (no failures, one replica) anchors every
migration test.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.launch.analysis import serving_summary
from repro.models import build_model
from repro.serve import (
    LLM,
    ReplicaLostError,
    Router,
    SamplingParams,
    ServeConfig,
)
from repro.serve.kvcache import PagedKVPool


@pytest.fixture(scope="module")
def model_and_params():
    cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


SC = ServeConfig(max_batch=2, page_size=4, hbm_pages=16, host_pages=32)
MAX_NEW = 8
N_REQ = 6


def prompts_for(cfg, n=N_REQ, seed=0, length=6):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(1, cfg.vocab, length)]
            for _ in range(n)]


def sampled(i, max_tokens=MAX_NEW):
    """Seeded non-greedy sampling: the strongest bitwise bar — a migrated
    request must resample identically, which only holds if seed AND
    absolute stream position survive the move."""
    return SamplingParams(temperature=0.8, top_k=40, top_p=0.9,
                          seed=100 + i, max_tokens=max_tokens)


def run_to_completion(llm, handles):
    steps = 0
    while any(not h.finished for h in handles):
        llm.step()
        steps += 1
        assert steps < 400, "cluster failed to converge (dropped request?)"
    return {h.request_id: (list(h.token_ids), h.finish_reason)
            for h in handles}


@pytest.fixture(scope="module")
def reference_streams(model_and_params):
    """The unkilled single-replica run every migration test compares to."""
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC)
    handles = [llm.submit(p, sampled(i), request_id=i)
               for i, p in enumerate(prompts_for(cfg))]
    return run_to_completion(llm, handles)


# ------------------------------------------------- pool export / import
def make_pool(seed=0):
    pool = PagedKVPool(n_layers=2, page_size=4, kv_heads=2, head_dim=8,
                       hbm_pages=8, host_pages=16, dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    pool.k_hbm = jnp.asarray(rng.normal(size=pool.k_hbm.shape), jnp.float32)
    pool.v_hbm = jnp.asarray(rng.normal(size=pool.v_hbm.shape), jnp.float32)
    for idx in range(3):
        page = pool.allocate(0, idx, step=0)
        page.tokens_used = 4 if idx < 2 else 2
        page.accesses = float(10 - idx)
    pool.swap_out(pool.request_pages(0)[2].page_id)   # one page on host
    return pool


def page_bytes(pool, page):
    if page.hbm_slot is not None:
        k, v = pool.k_hbm[:, page.hbm_slot], pool.v_hbm[:, page.hbm_slot]
    else:
        (k,), (v,) = pool.host.read([page.host_slot])
    return np.asarray(k).tobytes(), np.asarray(v).tobytes()


def test_export_import_roundtrip_bitwise_and_tier_preserving():
    src = make_pool()
    pages = src.request_pages(0)
    want = [page_bytes(src, p) for p in pages]
    export = src.export_pages([p.page_id for p in pages])
    assert src.exported_pages == 3
    assert export.fast == [True, True, False]      # source tiers recorded

    dst = PagedKVPool(n_layers=2, page_size=4, kv_heads=2, head_dim=8,
                      hbm_pages=8, host_pages=16, dtype=jnp.float32)
    landed = dst.import_pages(export, request_id=5, step=3)
    assert dst.imported_pages == 3
    assert [p.index_in_seq for p in landed] == [0, 1, 2]
    assert [p.tokens_used for p in landed] == [4, 4, 2]
    assert [p.accesses for p in landed] == [10.0, 9.0, 8.0]
    # Tier placement survives when the destination has room.
    assert [p.hbm_slot is not None for p in landed] == [True, True, False]
    assert [page_bytes(dst, p) for p in landed] == want
    assert [p.page_id for p in dst.request_pages(5)] == \
        [p.page_id for p in landed]


def test_export_unknown_page_and_geometry_mismatch_raise():
    src = make_pool()
    with pytest.raises(ValueError, match="999"):
        src.export_pages([999])
    export = src.export_pages([p.page_id for p in src.request_pages(0)])
    other = PagedKVPool(n_layers=2, page_size=8, kv_heads=2, head_dim=8,
                        hbm_pages=8, host_pages=16, dtype=jnp.float32)
    with pytest.raises(ValueError, match="share one model/page geometry"):
        other.import_pages(export, request_id=1, step=0)


def test_import_into_full_pool_raises_memoryerror_for_cold_fallback():
    src = make_pool()
    export = src.export_pages([p.page_id for p in src.request_pages(0)])
    tiny = PagedKVPool(n_layers=2, page_size=4, kv_heads=2, head_dim=8,
                       hbm_pages=1, host_pages=1, dtype=jnp.float32)
    with pytest.raises(MemoryError, match="cold-migrate instead"):
        tiny.import_pages(export, request_id=1, step=0)
    assert tiny.pages == {} and tiny.imported_pages == 0


def test_select_from_keeps_only_trailing_blocks():
    src = make_pool()
    export = src.export_pages([p.page_id for p in src.request_pages(0)])
    tail = export.select_from(2)
    assert len(tail) == 1 and tail.index_in_seq == [2]
    assert tail.k.shape[1] == 1


# ------------------------------------------------------------- dispatch
def test_least_loaded_dispatch_round_robins_fresh_cluster(model_and_params):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=3)
    for i, p in enumerate(prompts_for(cfg)):
        llm.submit(p, SamplingParams(max_tokens=MAX_NEW), request_id=i)
    owners = [llm.cluster.owner[i].replica_id for i in range(N_REQ)]
    assert owners == [0, 1, 2, 0, 1, 2]     # pages-in-use ties broken by id

    # Pinning overrides the balance; pinning to a non-alive replica raises.
    llm2 = LLM(model, params, SC, replicas=2)
    llm2.submit(prompts_for(cfg)[0], SamplingParams(max_tokens=2),
                request_id=0, replica_id=1)
    assert llm2.cluster.owner[0].replica_id == 1
    llm2.cluster.fail(0)
    with pytest.raises(ValueError, match="failed"):
        llm2.submit(prompts_for(cfg)[1], SamplingParams(max_tokens=2),
                    request_id=1, replica_id=0)


def test_n3_cluster_matches_single_replica_bitwise(model_and_params,
                                                   reference_streams):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=3)
    handles = [llm.submit(p, sampled(i), request_id=i)
               for i, p in enumerate(prompts_for(cfg))]
    assert run_to_completion(llm, handles) == reference_streams


# ------------------------------------------------------ cold migration
def test_replica_crash_cold_migrates_bitwise(model_and_params,
                                             reference_streams):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=3, heartbeat_timeout=2.0)
    handles = [llm.submit(p, sampled(i), request_id=i)
               for i, p in enumerate(prompts_for(cfg))]
    for _ in range(2):
        llm.step()
    victim = llm.cluster.replicas[0].replica_id
    orphaned = sorted(rid for rid, rep in llm.cluster.owner.items()
                      if rep.replica_id == victim)
    llm.cluster.fail(victim)
    assert run_to_completion(llm, handles) == reference_streams
    assert llm.cluster.failovers == 1
    assert llm.cluster.migrations_cold == len(orphaned)
    assert llm.cluster.requests_lost == 0
    # The failed member is gone; its requests decode on survivors.
    assert [r.replica_id for r in llm.cluster.replicas] == [1, 2]


def test_finished_before_crash_result_survives_via_ticket(model_and_params):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=2, heartbeat_timeout=2.0)
    llm.submit(prompts_for(cfg)[0], SamplingParams(max_tokens=3),
               request_id=9)
    steps = 0
    while 9 not in llm.cluster.finished:
        llm.cluster.step()        # step the router directly: no handle drain
        steps += 1
        assert steps < 100
    llm.cluster.fail(llm.cluster.owner[9].replica_id)
    for _ in range(4):
        llm.cluster.step()        # detection + recovery
    req = llm.cluster.pop_finished(9)   # orphaned result, served anyway
    assert req.finish_reason == "length" and len(req.generated) == 3


# ------------------------------------------------------ warm migration
def test_drain_warm_migrates_pages_bitwise(model_and_params,
                                           reference_streams):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=3)
    handles = [llm.submit(p, sampled(i), request_id=i)
               for i, p in enumerate(prompts_for(cfg))]
    for _ in range(3):
        llm.step()
    victim = llm.cluster.replicas[0].replica_id
    n_owned = sum(1 for rep in llm.cluster.owner.values()
                  if rep.replica_id == victim)
    assert llm.cluster.drain(victim) == n_owned
    assert llm.cluster.migrations_warm == n_owned   # pages fit: all warm
    assert llm.cluster.migrations_cold == 0
    llm.cluster.remove_replica(victim)
    assert run_to_completion(llm, handles) == reference_streams
    assert llm.stats()["imported_pages"] > 0
    assert llm.cluster.requests_lost == 0


def test_drain_with_shared_prefix_pages_stays_bitwise(model_and_params):
    cfg, model, params = model_and_params
    sc = dataclasses.replace(SC, enable_prefix_cache=True,
                             min_prefix_pages=1)
    rng = np.random.default_rng(1)
    shared = [int(t) for t in rng.integers(1, cfg.vocab, 9)]
    p1 = shared + [int(t) for t in rng.integers(1, cfg.vocab, 3)]
    p2 = shared + [int(t) for t in rng.integers(1, cfg.vocab, 3)]

    def run(kill):
        llm = LLM(model, params, sc, replicas=2)
        rep0 = llm.cluster.replicas[0].replica_id
        h1 = llm.submit(p1, SamplingParams(max_tokens=6), request_id=0,
                        replica_id=rep0)
        run_to_completion(llm, [h1])    # seeds the prefix cache on rep0
        h2 = llm.submit(p2, SamplingParams(max_tokens=6), request_id=1,
                        replica_id=rep0)
        for _ in range(2):
            llm.step()
        # The in-flight request really holds shared prefix-cache pages.
        eng = llm.cluster.owner[1].engine
        assert any(p.shared for p in eng.pool.request_pages(1))
        if kill:
            llm.cluster.drain(rep0)
            llm.cluster.remove_replica(rep0)
            assert llm.cluster.migrations_warm == 1
        return run_to_completion(llm, [h2])

    assert run(kill=True) == run(kill=False)


def test_warm_import_that_cannot_fit_falls_back_cold(model_and_params,
                                                     reference_streams):
    cfg, model, params = model_and_params
    # The survivor's pool is big enough to DECODE one request at a time
    # (preemption handles the rest) but too small to absorb the drained
    # replica's pages wholesale on top of its own — so per-request warm
    # imports can raise MemoryError and fall back to cold recompute.
    tiny = dataclasses.replace(SC, hbm_pages=5, host_pages=2, max_batch=1)
    llm = LLM(model, params, tiny, replicas=2)
    handles = [llm.submit(p, sampled(i), request_id=i)
               for i, p in enumerate(prompts_for(cfg))]
    for _ in range(3):
        llm.step()
    victim = llm.cluster.replicas[0].replica_id
    llm.cluster.drain(victim)
    llm.cluster.remove_replica(victim)
    got = run_to_completion(llm, handles)
    assert llm.cluster.requests_lost == 0
    assert llm.cluster.migrations_cold >= 1     # at least one didn't fit
    # Streams still bitwise-equal: page_size/seeds match the reference run.
    assert got == reference_streams


# ------------------------------------------------------ rolling restart
def test_rolling_restart_under_load_zero_drops_bitwise(model_and_params,
                                                       reference_streams):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=3)
    all_prompts = prompts_for(cfg)
    handles = [llm.submit(p, sampled(i), request_id=i)
               for i, p in enumerate(all_prompts[:4])]
    for _ in range(2):
        llm.step()
    original = [r.replica_id for r in llm.cluster.replicas]
    for i, rep_id in enumerate(original):
        llm.cluster.restart_replica(rep_id)
        # Submissions keep landing while the restart sweeps the cluster.
        rid = 4 + i
        if rid < N_REQ:
            handles.append(llm.submit(
                all_prompts[rid], sampled(rid), request_id=rid))
        llm.step()
    assert run_to_completion(llm, handles) == reference_streams
    assert llm.cluster.restarts == 3
    assert llm.cluster.requests_lost == 0
    # Every original member was replaced by a fresh id.
    now = [r.replica_id for r in llm.cluster.replicas]
    assert not set(now) & set(original) and len(now) == 3


# ----------------------------------------------------------- loud loss
def test_remove_without_migration_raises_replica_lost(model_and_params):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=2)
    h = llm.submit(prompts_for(cfg)[0], SamplingParams(max_tokens=20),
                   request_id=7)
    llm.step()
    llm.cluster.remove_replica(llm.cluster.owner[7].replica_id,
                               migrate=False)
    with pytest.raises(ReplicaLostError, match="removed without migration"):
        for _ in h:
            pass
    assert llm.cluster.requests_lost == 1
    with pytest.raises(ReplicaLostError):
        llm.pause(7)


def test_crash_with_no_survivor_raises_replica_lost(model_and_params):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=1, heartbeat_timeout=2.0)
    h = llm.submit(prompts_for(cfg)[0], SamplingParams(max_tokens=20),
                   request_id=3)
    llm.step()
    llm.cluster.fail(llm.cluster.replicas[0].replica_id)
    with pytest.raises(ReplicaLostError, match="no alive replica"):
        for _ in h:
            pass
    assert llm.cluster.requests_lost == 1


def test_drain_requires_another_alive_replica(model_and_params):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=1)
    llm.submit(prompts_for(cfg)[0], SamplingParams(max_tokens=4),
               request_id=0)
    with pytest.raises(ValueError, match="no other alive"):
        llm.cluster.drain(0)
    assert llm.cluster.replicas[0].state == "alive"   # rolled back


# ------------------------------------------------- router transparency
def test_single_replica_delegates_engine_attributes(model_and_params):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=1)
    assert isinstance(llm.engine, Router)
    assert llm.engine.pool is llm.cluster.replicas[0].engine.pool
    assert llm.engine.cfg.page_size == SC.page_size

    multi = LLM(model, params, SC, replicas=2)
    with pytest.raises(AttributeError, match="2 reachable replicas"):
        multi.engine.pool


def test_cluster_serving_summary_aggregates_and_nests(model_and_params):
    cfg, model, params = model_and_params
    llm = LLM(model, params, SC, replicas=2)
    handles = [llm.submit(p, SamplingParams(max_tokens=4), request_id=i)
               for i, p in enumerate(prompts_for(cfg, n=4))]
    run_to_completion(llm, handles)
    s = serving_summary(llm.cluster)
    assert s["cluster_replicas"] == 2
    assert set(s["replicas"]) == {"replica0", "replica1"}
    per = s["replicas"]
    assert s["engine_steps"] == sum(r["engine_steps"] for r in per.values())
    assert s["engine_finished_length"] == 4
    # At N=1 the summary is flat — same shape the pre-cluster tooling read.
    solo = LLM(model, params, SC, replicas=1)
    hs = [solo.submit(prompts_for(cfg)[0], SamplingParams(max_tokens=2),
                      request_id=0)]
    run_to_completion(solo, hs)
    flat = serving_summary(solo.cluster)
    assert "replicas" not in flat and flat["cluster_replicas"] == 1


def test_replicas_get_their_own_devices_and_drain_across_them():
    """``LLM(replicas=4)`` on four devices puts replica i's params and
    pools on device i; draining one warm-migrates its pages to replicas on
    other devices, and every stream stays bitwise equal to one replica."""
    from test_dist_collectives import run_in_subprocess

    out = run_in_subprocess("""
        import dataclasses
        from repro.configs import get_smoke
        from repro.models import build_model
        from repro.serve import LLM, SamplingParams, ServeConfig

        cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        sc = ServeConfig(max_batch=2, page_size=4, hbm_pages=16,
                         host_pages=32, max_pages_per_seq=8)
        rng = np.random.default_rng(0)
        prompts = [[int(t) for t in rng.integers(1, cfg.vocab, n)]
                   for n in (9, 6, 11, 7, 5, 10, 8, 6)]

        def run(replicas, drain_at=None):
            llm = LLM(model, params, sc, replicas=replicas)
            hs = [llm.submit(p, SamplingParams(max_tokens=6), request_id=i)
                  for i, p in enumerate(prompts)]
            steps, exported = 0, 0
            while not all(h.finished for h in hs):
                if steps == drain_at:
                    victim = max(llm.cluster.replicas,
                                 key=lambda r: len(r.engine.requests))
                    llm.cluster.drain(victim.replica_id)
                    exported = victim.engine.pool.exported_pages
                llm.step()
                steps += 1
            return llm, [h.result().token_ids for h in hs], exported

        _, want, _ = run(1)
        llm, got, exported = run(4, drain_at=2)
        placement = []
        for rep in llm.cluster.replicas:
            e = rep.engine
            devs = {e.device} | e.pool.k_hbm.devices()
            for a in jax.tree.leaves(e.params):
                devs |= a.devices()
            placement.append(devs)
        assert all(len(d) == 1 for d in placement), placement
        assert len(set.union(*placement)) == 4, placement
        assert exported > 0
        assert llm.cluster.migrations_warm > 0
        assert got == want
        print("replicas on own devices OK")
    """, devices=4)
    assert "replicas on own devices OK" in out
