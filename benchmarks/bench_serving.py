"""Serving-engine benchmark: guided KV-page tiering (the paper's technique
applied to serving) vs LRU/FIFO eviction on a multi-session workload with an
HBM page budget, a prefill-throughput case comparing one-shot paged prefill
(a single jitted dispatch per prompt) against the chunked per-token oracle,
a generation-API case measuring in-dispatch sampling overhead (sampled
vs greedy decode tokens/s) plus streaming time-to-first-delta through
``LLM.submit``, a prefix-cache sweep measuring TTFT on a
shared-system-prompt workload as the cached share of the prompt rises,
kill-a-replica chaos, and an SLO replay case: a deterministic two-tenant
bursty trace (``serve.workload``) replayed with one-shot vs interleaved
chunked prefill, scored as p50/p99 TTFT/TPOT and goodput-under-SLO on the
modeled step clock with a bitwise-vs-unloaded stream check.
``derived`` = page-swap bytes moved (lower is better) for swap rows,
modeled step time (PCIe swaps + decode) for time rows, prompt tokens/s for
prefill-throughput rows, seconds for TTFT rows, decode tokens/s for
sampled-decode rows, counts for finish-reason rows, hit-rate /
saved-token figures for the prefix sweep, and modeled-ms latencies /
goodput fractions / a 0-or-1 bitwise flag for the SLO replay rows."""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_smoke
from repro.core import TPU_V5E
from repro.launch.analysis import serving_summary
from repro.models import build_model
from repro.serve import (
    LLM,
    SLO,
    SamplingParams,
    ServeConfig,
    TenantSpec,
    TraceReplayer,
    WorkloadConfig,
    synthesize,
)

from .common import emit


def _smoke_model():
    cfg = dataclasses.replace(get_smoke("llama3_2_1b"), remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def session_workload(policy: str, rounds: int = 10):
    """Hot multi-turn sessions + periodic one-shot 'scan' sessions (long
    prompt, generated once, never resumed) — the access pattern where
    frequency-aware guidance must resist cache pollution.  Driven entirely
    through the ``LLM`` front door."""
    cfg, model, params = _smoke_model()
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=12, host_pages=160,
        policy=policy, interval_steps=4))
    rng = np.random.default_rng(0)
    prompt = [2, 7, 1, 8, 2, 8]
    for rid in range(4):
        llm.submit(prompt, SamplingParams(max_tokens=64), request_id=rid)
        llm.pause(rid)
    hot = [0, 1]
    scan_id = 1000
    t0 = time.perf_counter()
    for r in range(rounds):
        for rid in hot:
            if llm.is_live(rid):
                llm.resume(rid)
        extra = 2 + (r // 5) % 2
        if r % 5 == 4 and llm.is_live(extra):
            llm.resume(extra)
        for _ in range(2):
            llm.step()
        if r % 2 == 1:
            # scan: long one-shot request, decoded briefly, then abandoned
            long_prompt = [int(t) for t in rng.integers(1, cfg.vocab, 16)]
            llm.submit(long_prompt, SamplingParams(max_tokens=2),
                       request_id=scan_id)
            llm.step()
            llm.step()
            scan_id += 1
        for rid in list(llm.engine.requests):
            if llm.engine.requests[rid].state == "active":
                llm.pause(rid)
    wall = time.perf_counter() - t0
    return serving_summary(llm.engine), wall


def prefill_throughput(mode: str, prompt_len: int):
    """Prompt-ingestion cost for one prefill mode: prompt tokens/s of the
    ingest itself and wall-clock time-to-first-token (ingest + one decode
    step), measured after a warm-up request compiles both paths."""
    _, model, params = _smoke_model()
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=64, host_pages=64,
        policy="gdt", interval_steps=8, prefill=mode,
        max_pages_per_seq=max(32, prompt_len // 4 + 2)))
    eng = llm.engine
    rng = np.random.default_rng(1)
    warm = [int(t) for t in rng.integers(1, 256, prompt_len)]
    llm.submit(warm, SamplingParams(max_tokens=1), request_id=0)  # compile
    while llm.is_live(0):
        llm.step()
    prompt = [int(t) for t in rng.integers(1, 256, prompt_len)]
    d0 = eng.prefill_dispatches
    t0 = time.perf_counter()
    handle = llm.submit(prompt, SamplingParams(max_tokens=2), request_id=1)
    # Block on the KV pools: the one-shot path is a single async jitted
    # dispatch, so without a sync the timer would measure dispatch
    # overhead, not the ingest itself (chunked syncs every token anyway).
    jax.block_until_ready(eng.pool.hbm)
    t_ingest = time.perf_counter() - t0
    handle.next_delta()                   # streaming first token
    ttft = time.perf_counter() - t0
    dispatches = eng.prefill_dispatches - d0
    tokens_per_s = (prompt_len - 1) / t_ingest if t_ingest else float("inf")
    return tokens_per_s, ttft, dispatches, t_ingest


def sampled_decode(temperature: float, n_requests: int = 4,
                   max_tokens: int = 16):
    """Generation-API decode throughput at one temperature: submit
    ``n_requests`` streaming handles, record time-to-first-delta on the
    first, then drain everything.  ``temperature=0`` is the greedy
    baseline the sampled run's overhead is reported against."""
    _, model, params = _smoke_model()
    llm = LLM(model, params, ServeConfig(
        max_batch=4, page_size=4, hbm_pages=48, host_pages=64,
        policy="gdt", interval_steps=8))
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(1, 256, 8)]
               for _ in range(n_requests)]
    sp = [SamplingParams(temperature=temperature, top_k=40, top_p=0.9,
                         seed=i, max_tokens=max_tokens)
          for i in range(n_requests)]
    # Warm-up: compile the decode dispatch for this batch shape.
    llm.generate(prompts[0], SamplingParams(temperature=temperature,
                                            top_k=40, top_p=0.9,
                                            max_tokens=2))
    # Finish-reason counters are monotonic: baseline after the warm-up so
    # the emitted counts cover exactly the measured requests.
    base = llm.stats()
    t0 = time.perf_counter()
    handles = [llm.submit(p, s) for p, s in zip(prompts, sp)]
    handles[0].next_delta()
    ttfd = time.perf_counter() - t0
    outs = [h.result() for h in handles]
    wall = time.perf_counter() - t0
    tokens = sum(len(o.token_ids) for o in outs)
    stats = llm.stats()
    reasons = {r: stats[f"finished_{r}"] - base[f"finished_{r}"]
               for r in ("stop", "length", "truncated")}
    return tokens / wall, ttfd, reasons, wall


def prefix_share_ttft(share: float, prompt_len: int, page_size: int = 4):
    """TTFT on a shared-system-prompt workload at one prefix share.

    A seeder request populates the radix cache with the shared prefix
    (``share`` of the prompt, page-aligned); a warm-up request with the
    same share compiles the suffix's jit bucket AND exercises the hit path;
    the measured request then covers ``share`` of its prompt from the cache
    and prefills only the suffix — TTFT should fall ~linearly as the share
    rises (a full hit skips the prefill dispatch entirely)."""
    _, model, params = _smoke_model()
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=page_size, hbm_pages=64, host_pages=64,
        policy="gdt", interval_steps=8, enable_prefix_cache=True,
        max_pages_per_seq=max(32, prompt_len // page_size + 2)))
    eng = llm.engine
    rng = np.random.default_rng(3)
    # Page-align the shared span: sharing is full-page granular.
    shared_pages = int(share * prompt_len) // page_size
    n_shared = shared_pages * page_size
    shared_prefix = [int(t) for t in rng.integers(1, 256, n_shared)]

    def prompt_with_tail(seed: int):
        tail = [int(t) for t in
                np.random.default_rng(seed).integers(1, 256,
                                                     prompt_len - n_shared)]
        return shared_prefix + tail

    for rid, seed in ((0, 100), (1, 101)):     # seeder, then bucket warm-up
        llm.submit(prompt_with_tail(seed), SamplingParams(max_tokens=1),
                   request_id=rid)
        while llm.is_live(rid):
            llm.step()
    base_saved = eng.saved_prefill_tokens
    # Best-of-3 distinct-tail trials: CPU dispatch jitter is the same order
    # as a short suffix's ingest, so a single sample can invert the trend.
    ttft = float("inf")
    for trial, seed in enumerate((102, 103, 104)):
        t0 = time.perf_counter()
        handle = llm.submit(prompt_with_tail(seed),
                            SamplingParams(max_tokens=2),
                            request_id=2 + trial)
        jax.block_until_ready(eng.pool.hbm)
        handle.next_delta()
        ttft = min(ttft, time.perf_counter() - t0)
        while llm.is_live(2 + trial):
            llm.step()
    saved = (eng.saved_prefill_tokens - base_saved) / 3
    return ttft, eng.prefix_cache.hit_rate, saved


def cluster_chaos(n_replicas: int = 3, n_requests: int = 9,
                  max_tokens: int = 6, kill_step: int = 3,
                  heartbeat_timeout: float = 2.0):
    """Kill-a-replica chaos under continuous submit load: one of
    ``n_replicas`` replicas crashes mid-decode, the router detects the
    missed heartbeats and cold-migrates its in-flight requests to the
    survivors.  Reports requests dropped (the zero-drop contract), p99
    time-to-first-token across the run (the failover window shows up as
    the TTFT tail of requests stalled on the dead replica), and the
    migration counters."""
    _, model, params = _smoke_model()
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=24, host_pages=64,
        policy="gdt", interval_steps=8), replicas=n_replicas,
        heartbeat_timeout=heartbeat_timeout)
    rng = np.random.default_rng(5)
    handles, submit_t, first_t = {}, {}, {}
    next_rid = 0

    def submit_one():
        nonlocal next_rid
        prompt = [int(t) for t in rng.integers(1, 256, 6)]
        submit_t[next_rid] = time.perf_counter()
        handles[next_rid] = llm.submit(
            prompt, SamplingParams(max_tokens=max_tokens),
            request_id=next_rid)
        next_rid += 1

    for _ in range(n_replicas):
        submit_one()
    killed = False
    steps = 0
    while (next_rid < n_requests
           or any(not h.finished for h in handles.values())):
        if steps == kill_step and not killed:
            llm.cluster.fail(llm.cluster.replicas[0].replica_id)
            killed = True
        if next_rid < n_requests and steps % 2 == 0:
            submit_one()
        llm.step()
        now = time.perf_counter()
        for rid, h in handles.items():
            if rid not in first_t and h.token_ids:
                first_t[rid] = now
        steps += 1
        if steps > 500:      # chaos must converge; a hang is a bug signal
            break
    dropped = sum(1 for h in handles.values() if not h.finished)
    ttfts = sorted(first_t[rid] - submit_t[rid] for rid in first_t)
    p99 = float(np.percentile(ttfts, 99)) if ttfts else float("inf")
    stats = llm.stats()
    return dropped, p99, stats


def _moe_smoke_model():
    """The MoE smoke config at 4 layers instead of 2.  Layer-ahead expert
    prefetch can never predict the wrap-around dispatch (the next step's
    first layer routes a token that does not exist yet), so the 2-layer
    smoke stack would charge HALF of all dispatches to that blind spot —
    the real granite_moe config has 32 layers, where it is 1/32.  Four
    layers keep the CPU cost small without the pathological handicap."""
    cfg = dataclasses.replace(get_smoke("granite_moe_3b_a800m"),
                              remat=False, n_layers=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def expert_tiering_decode(cache_frac: float, rounds: int):
    """Decode throughput with MoE expert FFN weights host-resident behind
    a bounded HBM cache holding ``cache_frac`` of all (layer, expert)
    blocks, with the double-buffered speculative prefetch.
    Rotating session pairs churn the routed expert mix so the cache
    actually turns over.  Returns the tokens, the host seconds they took
    and the store's fetch counts."""
    cfg, model, params = _moe_smoke_model()
    total = cfg.n_layers * cfg.n_experts
    C = max(4, int(round(total * cache_frac)))
    llm = LLM(model, params, ServeConfig(
        max_batch=2, page_size=4, hbm_pages=48, host_pages=96,
        policy="gdt", interval_steps=16, prefill_chunk_tokens=2,
        expert_offchip=True, expert_cache_size=C))
    store = llm.engine.expert_store
    rng = np.random.default_rng(9)
    n_sessions = 6
    for rid in range(n_sessions):
        prompt = [int(t) for t in rng.integers(1, cfg.vocab, 6)]
        llm.submit(prompt, SamplingParams(
            temperature=0.8, top_k=4, seed=rid,
            max_tokens=4 * rounds + 16), request_id=rid)
        if llm.engine.requests[rid].state == "active":
            llm.pause(rid)
    # Warm-up: compile every tiered dispatch shape, then zero the counters
    # so the measured window starts from identical resident state.
    llm.resume(0)
    llm.resume(1)
    for _ in range(4):
        llm.step()
    for rid in list(llm.engine.requests):
        if llm.engine.requests[rid].state == "active":
            llm.pause(rid)
    store.reset_counters()
    tokens = 0
    t0 = time.perf_counter()
    for r in range(rounds):
        for rid in (r % n_sessions, (r + 1) % n_sessions):
            if llm.is_live(rid):
                llm.resume(rid)
        for _ in range(3):
            tokens += len(llm.step())
        for rid in list(llm.engine.requests):
            if llm.engine.requests[rid].state == "active":
                llm.pause(rid)
    wall = time.perf_counter() - t0
    return dict(
        tokens=tokens, wall=wall, cache_slots=C,
        demand_fetches=store.demand_fetches,
        prefetch_fetches=store.prefetch_fetches,
        prefetch_hits=store.prefetch_hits,
        evictions=store.evictions)


def _slo_trace(quick: bool):
    """The smoke replay scenario: a decode-heavy 'chat' tenant (steady
    Poisson arrivals, short sampled completions) sharing the engine with a
    'batch' tenant whose bursts carry long prompts (the 32k-prefill
    problem scaled to the CPU smoke model).  Fully deterministic — one
    workload seed pins every arrival, length, and sampled stream."""
    long_prompt = 128 if quick else 256
    tenants = (
        TenantSpec(name="chat", arrival="poisson", rate=0.3,
                   prompt_mix=((6, 1.0),), output_mix=((16, 1.0),),
                   temperature=0.7),
        TenantSpec(name="batch", arrival="bursty", rate=0.05,
                   burst_factor=10.0, burst_period=16, burst_duty=0.25,
                   prompt_mix=((long_prompt, 1.0),),
                   output_mix=((2, 1.0),)),
    )
    trace = synthesize(WorkloadConfig(
        tenants=tenants, horizon_steps=32 if quick else 48, vocab=256,
        seed=8))
    return trace, long_prompt


def _slo_serve_cfg(long_prompt: int, chunk_tokens: int) -> ServeConfig:
    return ServeConfig(
        max_batch=6, page_size=8, hbm_pages=160, host_pages=64,
        policy="gdt", interval_steps=16,
        max_pages_per_seq=long_prompt // 8 + 4,
        prefill_chunk_tokens=chunk_tokens)


def _solo_reference(trace, long_prompt: int):
    """Unloaded per-request streams: each trace request runs ALONE (one
    reusable LLM, sequential submits) — sampling folds the absolute stream
    position, so any loaded schedule must reproduce these bitwise."""
    _, model, params = _smoke_model()
    llm = LLM(model, params, _slo_serve_cfg(long_prompt, 0))
    return {tr.request_id:
            llm.submit(list(tr.prompt), tr.sampling_params(),
                       request_id=tr.request_id).result().token_ids
            for tr in trace.requests}


def slo_replay(trace, long_prompt: int, chunk_tokens: int):
    """Replay the two-tenant trace at one prefill-interleaving setting and
    score it against the SLO on the modeled step clock (where a one-shot
    long prefill is VISIBLE as one 25-50x step, stalling every concurrent
    decode's inter-token gap)."""
    _, model, params = _smoke_model()
    llm = LLM(model, params, _slo_serve_cfg(long_prompt, chunk_tokens))
    slo = SLO(ttft_ms=100.0, tpot_ms=25.0)
    report = TraceReplayer(llm, trace, slo=slo).run(max_steps=2048)
    return report, slo


def run(quick: bool = False):
    rows = []
    pcie = TPU_V5E.slow.read_bw_GBps * 1e9
    for policy in ("gdt", "lru", "fifo"):
        summary, wall = session_workload(policy, rounds=6 if quick else 10)
        bytes_moved = summary["engine_bytes_moved"]
        swap_s = bytes_moved / pcie
        rows.append((f"serve/{policy}/swap_bytes", wall * 1e6, bytes_moved))
        rows.append((f"serve/{policy}/swap_ins", wall * 1e6,
                     summary["engine_swap_ins"]))
        rows.append((f"serve/{policy}/modeled_swap_seconds", wall * 1e6,
                     swap_s))
        rows.append((f"serve/{policy}/transfer_events", wall * 1e6,
                     summary["engine_transfer_events"]))
        rows.append((f"serve/{policy}/preemptions", wall * 1e6,
                     summary["engine_preemptions"]))
        if "migrations" in summary:  # the controller's own event stream
            rows.append((f"serve/{policy}/guided_migrations", wall * 1e6,
                         summary["migrations"]))
            rows.append((f"serve/{policy}/guided_rental_bytes", wall * 1e6,
                         summary["rental_bytes"]))
            rows.append((f"serve/{policy}/dropped_promotions", wall * 1e6,
                         summary["dropped_promotions"]))
    prompt_len = 32 if quick else 96
    for mode in ("one_shot", "chunked"):
        tps, ttft, dispatches, t_ingest = prefill_throughput(mode, prompt_len)
        rows.append((f"serve/prefill/{mode}/tokens_per_s",
                     t_ingest * 1e6, tps))
        rows.append((f"serve/prefill/{mode}/ttft_seconds",
                     ttft * 1e6, ttft))
        rows.append((f"serve/prefill/{mode}/dispatches",
                     t_ingest * 1e6, dispatches))
    # Prefix-cache sweep: TTFT on a shared-system-prompt workload should
    # fall ~linearly as the cached share of the prompt rises (the suffix
    # is all that prefills).  ``derived`` = seconds for ttft rows, cache
    # hit rate for hit_rate rows, prompt tokens served from the cache for
    # saved_tokens rows.
    # Long enough that ingest compute (linear in the uncovered suffix)
    # outweighs per-dispatch overhead even on the CPU smoke model.
    sweep_len = max(prompt_len, 64)
    for share in (0.0, 0.5, 1.0):
        ttft, hit_rate, saved = prefix_share_ttft(share, sweep_len)
        tag = f"serve/prefix_share/{share:.1f}"
        rows.append((f"{tag}/ttft_seconds", ttft * 1e6, ttft))
        rows.append((f"{tag}/hit_rate", 0.0, hit_rate))
        rows.append((f"{tag}/saved_tokens", 0.0, float(saved)))
    # Generation API: sampled vs greedy decode through LLM.submit handles.
    max_tokens = 8 if quick else 16
    results = {}
    for name, temp in (("greedy", 0.0), ("sampled", 0.8)):
        tps, ttfd, reasons, wall = sampled_decode(temp,
                                                  max_tokens=max_tokens)
        results[name] = tps
        rows.append((f"serve/generate/{name}/tokens_per_s", wall * 1e6, tps))
        rows.append((f"serve/generate/{name}/ttfd_seconds", ttfd * 1e6,
                     ttfd))
        for reason in ("stop", "length", "truncated"):
            rows.append((f"serve/generate/{name}/finished_{reason}",
                         wall * 1e6, reasons[reason]))
    # In-dispatch sampling overhead: greedy tokens/s over sampled tokens/s
    # (~1.0 when the Gumbel/top-k/top-p epilogue fuses cleanly).
    rows.append(("serve/generate/sampling_overhead_x", 0.0,
                 results["greedy"] / max(results["sampled"], 1e-9)))
    # Kill-a-replica chaos: the zero-drop contract under failover, with the
    # failover window visible as the p99 TTFT tail.  ``derived`` = dropped
    # requests / seconds / event counts respectively.
    dropped, p99_ttft, cstats = cluster_chaos(
        n_requests=6 if quick else 9)
    rows.append(("serve/chaos/requests_dropped", 0.0, float(dropped)))
    rows.append(("serve/chaos/p99_ttft_seconds", p99_ttft * 1e6, p99_ttft))
    rows.append(("serve/chaos/failovers", 0.0,
                 cstats["cluster_failovers"]))
    rows.append(("serve/chaos/migrations_cold", 0.0,
                 cstats["cluster_migrations_cold"]))
    rows.append(("serve/chaos/requests_lost", 0.0,
                 cstats["cluster_requests_lost"]))
    # Expert-weight tiering: MoE decode with expert FFN blocks behind a
    # bounded HBM cache, swept over the cached fraction of all blocks,
    # with the double-buffered (db) speculative prefetch.  ``derived`` =
    # wall decode tokens/s for tokens_per_s rows, counts for fetch/hit
    # rows.  What a fetch costs on the chip is read from a trace
    # (``repro.expert.*`` spans), not here.
    ex_rounds = 8 if quick else 12
    for frac in (1.0, 0.5, 0.25):
        r = expert_tiering_decode(frac, ex_rounds)
        tag = f"serve/expert_tiering/frac{frac:g}/db"
        rows.append((f"{tag}/decode_tokens_per_s", r["wall"] * 1e6,
                     r["tokens"] / r["wall"]))
        rows.append((f"{tag}/demand_fetches", r["wall"] * 1e6,
                     r["demand_fetches"]))
        rows.append((f"{tag}/prefetch_hits", r["wall"] * 1e6,
                     r["prefetch_hits"]))
    # SLO replay: bursty two-tenant trace, FIFO one-shot vs FIFO with
    # chunked-prefill interleaving.  ``derived`` = modeled milliseconds
    # for latency rows, fractions for goodput rows, and a 0/1 flag for the
    # bitwise-vs-unloaded check; ``us_per_call`` = the replay's total
    # modeled time.  The headline is chat_p99_tpot_ms: the decode-heavy
    # tenant's worst inter-token stall under the batch tenant's
    # long-prefill bursts must IMPROVE when interleaving is on, while
    # every sampled stream stays bitwise-equal to its unloaded solo run.
    trace, long_prompt = _slo_trace(quick)
    ref_streams = _solo_reference(trace, long_prompt)
    for label, chunk in (("fifo_oneshot", 0), ("fifo_chunked", 16)):
        report, slo = slo_replay(trace, long_prompt, chunk)
        s_all = report.summary(slo=slo)
        s_chat = report.summary(tenant="chat", slo=slo)
        us = report.modeled_ms * 1e3
        tag = f"serve/slo_replay/{label}"
        rows.append((f"{tag}/p50_ttft_ms", us, s_all["p50_ttft_ms"]))
        rows.append((f"{tag}/p99_ttft_ms", us, s_all["p99_ttft_ms"]))
        rows.append((f"{tag}/p50_tpot_ms", us, s_all["p50_tpot_ms"]))
        rows.append((f"{tag}/p99_tpot_ms", us, s_all["p99_tpot_ms"]))
        rows.append((f"{tag}/chat_p99_tpot_ms", us,
                     s_chat["p99_tpot_ms"]))
        rows.append((f"{tag}/goodput_slo", us, s_all["goodput_slo"]))
        rows.append((f"{tag}/chat_goodput_slo", us,
                     s_chat["goodput_slo"]))
        rows.append((f"{tag}/streams_bitwise_equal", 0.0, float(
            all(report.token_ids.get(rid) == toks
                for rid, toks in ref_streams.items()))))
    return emit(rows)


if __name__ == "__main__":
    run()
