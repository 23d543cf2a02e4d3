#!/usr/bin/env python3
"""Chip smoke test: serve granite-moe-3b-a800m at its published widths on
one TPU v5e through ``LLM``, with KV pages and expert blocks moving to
pinned host memory and back.

    python chip_smoke.py              # one chip: phases A, reference, B
    python chip_smoke.py --chips 4    # four chips: replicas behind the
                                      # router, a drain that warm-migrates

Weights are random from ``--seed``; prompts are random token ids from the
same seed.  One process drives every phase and starts no child.  Each phase
prints one JSON line; any failed check exits nonzero without the final
line.  The last line of a passing run is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

Phases on one chip:

* **A, paged**: 16 sessions of 1,024-2,048 prompt tokens, ``max_new`` 32,
  paused and resumed in rotating groups as ``launch/serve.py`` does, with
  an HBM page pool (1,024 pages of 1 MiB) about half of what the sessions
  hold, so pages leave for ``pinned_host`` and come back.
* **A, resident**: the same requests with a pool that holds them all;
  streams must be bitwise equal to the paged run's.
* **Reference**: the paged run's logits for one request (its first decode
  steps) against the model's own non-paged forward pass in float32.
* **B, experts off the chip**: the same requests with the expert weights
  on ``pinned_host`` behind a 640-block HBM cache (half the 1,280 blocks),
  then behind a 320-block one; the two placements' streams must be
  bitwise equal, and each run's logits within tolerance of the float32
  reference.  (The tiered pipeline is a different program from the
  resident scan; on the TPU their roundings differ, so their streams are
  compared and printed, not required equal.)

Nothing printed here is a rate or a modeled time: the device numbers are
compile seconds and peak device memory as JAX reports them.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite_moe_3b_a800m"
PROBE = "tpu_custom_call"       # what a Pallas kernel lowers to on the TPU
# Logits of the engine (bfloat16 weights and activations, Pallas kernels)
# against the float32 reference: relative L2 error over the compared rows.
# bfloat16 keeps 8 significand bits (rounding error 2**-9 per operation);
# the network is not chaotic at the weights ``init_weights`` draws, so the
# error stays near the rounding of the last layers: about 0.02 at full
# width with 4 and with 8 layers in float32-vs-bfloat16 runs on the CPU.
# A wrong page, slot or expert decorrelates the logits (error of order 1),
# so 0.15 separates the two with room on both sides.
REF_TOL = 0.15


@dataclasses.dataclass(frozen=True)
class Sizes:
    sessions: int = 16
    prompt_min: int = 1024
    prompt_max: int = 2048
    max_new: int = 32
    max_batch: int = 8
    page_size: int = 16
    paged_hbm_pages: int = 1024      # 1 GiB of KV at granite widths
    host_pages: int = 4096
    max_pages_per_seq: int = 132     # 2,112 tokens: the longest session
    expert_cache: int = 640          # half of 32 layers x 40 experts
    ref_steps: int = 4               # decode steps checked against f32
    steps_per_round: int = 4


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**kv) -> None:
    print(json.dumps(kv, default=str), flush=True)


class CompileClock:
    """Backend compile seconds, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.total += secs


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------- workload
def init_weights(model, seed: int):
    """Random weights from ``seed``: the model's own initializer, with the
    attention projections rescaled to the fan-in they contract.
    ``models/common.py`` takes every weight's fan-in from its second-last
    axis, which for the (d, heads, dh) projections is the head count; random
    attention logits then have a standard deviation near 64, softmax
    saturates, and a 32-layer pass turns chaotic: a 1e-6 perturbation of
    the input reaches order 1, so bfloat16 rounding alone would decorrelate
    the engine from any reference.  At these scales it stays near 1e-4."""
    import math

    import jax
    import jax.numpy as jnp

    acfg = model.attn_cfg
    d, H, K = acfg.d_model, acfg.n_heads, acfg.kv_heads
    factors = {"wq": math.sqrt(H / d), "wk": math.sqrt(K / d),
               "wv": math.sqrt(K / d), "wo": math.sqrt(1 / H)}

    def init(key):
        params = model.init(key)
        attn = params["layers"]["attn"]
        for name, f in factors.items():
            attn[name] = (attn[name].astype(jnp.float32) * f).astype(
                attn[name].dtype)
        return params

    return jax.block_until_ready(jax.jit(init)(jax.random.PRNGKey(seed)))


def make_prompts(sizes: Sizes, vocab: int, seed: int) -> List[List[int]]:
    import numpy as np

    rng = np.random.default_rng(seed)
    lens = rng.integers(sizes.prompt_min, sizes.prompt_max + 1,
                        sizes.sessions)
    return [[int(t) for t in rng.integers(1, vocab, int(n))] for n in lens]


def stream_diff(got, want) -> Dict[int, int]:
    """Request index -> first token position where two runs' streams
    part (empty when they are bitwise equal)."""
    out = {}
    for i, (g, w) in enumerate(zip(got, want)):
        if g != w:
            out[i] = next((j for j, (x, y) in enumerate(zip(g, w))
                           if x != y), min(len(g), len(w)))
    return out


def pages_held(prompts, sizes: Sizes) -> int:
    """KV pages the sessions hold when every one has finished decoding."""
    return sum(-(-(len(p) - 1 + sizes.max_new) // sizes.page_size)
               for p in prompts)


def serve_config(sizes: Sizes, hbm_pages: int, **kw):
    from repro.serve import ServeConfig

    return ServeConfig(
        max_batch=sizes.max_batch, page_size=sizes.page_size,
        hbm_pages=hbm_pages, host_pages=sizes.host_pages, policy="gdt",
        max_pages_per_seq=sizes.max_pages_per_seq, **kw)


def run_sessions(llm, prompts, sizes: Sizes, track: Optional[int] = None,
                 on_round: Optional[Callable[[int, int], None]] = None,
                 label: str = ""):
    """Submit every prompt, then pause and resume the sessions in rotating
    groups of ``max_batch`` (the ``launch/serve.py`` pattern) until all
    streams finish.  Returns the streams and, for ``track``, the logits of
    its first ``ref_steps`` decode steps."""
    from repro.serve import SamplingParams

    handles = []
    for rid, prompt in enumerate(prompts):
        handles.append(llm.submit(
            prompt, SamplingParams(max_tokens=sizes.max_new),
            request_id=rid))
        if llm.engine.requests[rid].state == "active":
            llm.pause(rid)
    logits: List = []
    rnd = 0
    generated = 0
    t0 = time.perf_counter()
    while True:
        print(f"[{label}] round {rnd}: {generated} tokens, "
              f"{time.perf_counter() - t0:.1f} s", file=sys.stderr,
              flush=True)
        if on_round is not None:
            on_round(rnd, generated)
        live = [rid for rid in range(len(prompts)) if llm.is_live(rid)]
        if not live:
            break
        start = (rnd * sizes.max_batch) % len(live)
        for rid in (live[start:] + live[:start])[:sizes.max_batch]:
            llm.resume(rid)
        for _ in range(sizes.steps_per_round):
            out = llm.step()
            generated += len(out)
            if track in out and len(logits) < sizes.ref_steps:
                logits.append(llm.cluster.replicas[0].engine
                              .last_logits[track].copy())
        for rid, req in list(llm.engine.requests.items()):
            if req.state == "active":
                llm.pause(rid)
        rnd += 1
    return [h.result().token_ids for h in handles], logits


# ------------------------------------------------------------------ checks
def kernel_check(engine, sizes: Sizes, tiered: bool) -> Dict[str, bool]:
    """Compile the programs the phase ran (a persistent-cache hit after
    the run) and report whether each holds a Pallas TPU kernel."""
    import jax
    import jax.numpy as jnp

    B, MP = sizes.max_batch, sizes.max_pages_per_seq
    S = 1 << (sizes.prompt_max - 1).bit_length()
    sd = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    pools, p = engine.pool.hbm, engine.params
    dt = pools[0].dtype
    if not tiered:
        progs = {
            "decode": engine._decode_greedy.lower(
                p, pools, sd((B, 1), i32), sd((B, MP), i32), sd((B,), i32),
                sd((B,), i32), sd((B,), i32), sd((B,), bool), sd((B,), i32),
                sd((B,), f32), sd((B,), i32), sd((B,), f32)),
            "prefill": engine._prefill.lower(
                p, pools, sd((S,), i32), sd((MP,), i32), sd((S,), i32),
                sd((S,), i32), sd((), i32), sd((), i32)),
        }
    else:
        d = engine.model.cfg.d_model
        st = engine.expert_store
        progs = {
            "decode_attn": engine._t_attn.lower(
                p, pools, sd((B, 1, d), dt), sd((), i32),
                sd((B, MP), i32), sd((B,), i32), sd((B,), i32),
                sd((B,), i32), sd((B,), bool), dispatch="decode"),
            "prefill_attn": engine._t_attn.lower(
                p, pools, sd((1, S, d), dt), sd((), i32),
                sd((MP,), i32), sd((S,), i32), sd((S,), i32), sd((S,), i32),
                sd((S,), i32), sd((S,), bool), dispatch="prefill"),
            "ffn": engine._t_ffn.lower(
                sd((B, 1, d), dt), sd((B, 1, d), dt),
                st.w_gate_cache, st.w_up_cache, st.w_down_cache,
                sd((st.n_experts,), i32),
                sd((B, engine.model.moe_cfg.top_k), f32),
                sd((B, engine.model.moe_cfg.top_k), i32),
                sd((B, 1, 1), bool)),
        }
    return {name: PROBE in lo.compile().as_text()
            for name, lo in progs.items()}


def reference_logits(model, params, tokens, first: int, n: int):
    """The model's own non-paged forward pass (``Model``'s layer body, as
    ``Model.prefill`` scans it) in float32 with the pure-jnp kernels and
    highest matmul precision, one layer at a time so that only one
    float32 layer sits beside the bfloat16 weights.  Returns the logits at
    positions ``first .. first+n-1``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models.layers import embed, lm_head, rmsnorm

    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    L = model.cfg.n_layers
    toks = jnp.asarray(tokens, jnp.int32)[None]
    positions = jnp.arange(toks.shape[1], dtype=jnp.int32)[None]
    ops.set_mode("reference")
    try:
        with jax.default_matmul_precision("highest"):
            layer = jax.jit(
                lambda lp, x: model._dense_body(x, f32(lp), positions))
            x = embed(f32(params["embed"]), toks)
            for l in range(L):
                lp = jax.tree.map(lambda a: a[l], params["layers"])
                x = layer(lp, x)
            x = rmsnorm(f32(params["final_ln"]), x[:, first:first + n])
            return jax.device_get(lm_head(f32(params["head"]), x)[0])
    finally:
        ops.set_mode(None)


# ------------------------------------------------------------------ phases
def phase_a(model, params, prompts, sizes: Sizes, clock, device,
            track: int):
    import numpy as np

    from repro.serve import LLM

    out = {}
    for name, hbm in (("paged", sizes.paged_hbm_pages),
                      ("resident", pages_held(prompts, sizes)
                       + sizes.max_batch + 1)):
        c0, t0 = clock.total, time.perf_counter()
        llm = LLM(model, params, serve_config(sizes, hbm, keep_logits=True))
        engine = llm.cluster.replicas[0].engine
        kinds: Dict[str, set] = {"hbm": set(), "host": set()}

        def watch(rnd, _gen):
            kinds["hbm"] |= {a.sharding.memory_kind
                             for a in engine.pool.hbm}
            kinds["host"] |= engine.pool.host.memory_kinds()

        streams, logits = run_sessions(llm, prompts, sizes, track=track,
                                       on_round=watch, label=f"A_{name}")
        stats = llm.stats()
        kernels = kernel_check(engine, sizes, tiered=False)
        emit(phase=f"A_{name}", hbm_pages=hbm,
             host_pages=sizes.host_pages, swaps_out=stats["swap_outs"],
             swaps_in=stats["swap_ins"],
             transfer_events=stats["transfer_events"],
             pool_memory_kinds={k: sorted(v) for k, v in kinds.items()},
             kernels=kernels, compile_seconds=clock.total - c0,
             phase_seconds=time.perf_counter() - t0,
             peak_bytes_in_use=peak_bytes(device),
             tokens=sum(len(s) for s in streams))
        check(all(kernels.values()), f"A_{name}: a program without {PROBE}")
        check(kinds["hbm"] == {"device"},
              f"A_{name}: HBM pool memory kinds {kinds['hbm']}")
        if name == "paged":
            check(stats["swap_outs"] > 0 and stats["swap_ins"] > 0,
                  "A_paged: no page left HBM and came back")
            check(kinds["host"] == {"pinned_host"},
                  f"A_paged: host tier memory kinds {kinds['host']}")
        check(all(len(s) == sizes.max_new for s in streams),
              f"A_{name}: a stream ended short")
        check(len(logits) == sizes.ref_steps,
              f"A_{name}: kept {len(logits)} logits rows")
        out[name] = (streams, np.stack(logits))
        del llm, engine
        gc.collect()
    diff = stream_diff(out["paged"][0], out["resident"][0])
    emit(phase="A_compare", streams_bitwise_equal=not diff,
         streams_differing=diff)
    check(not diff, "A: paged and resident streams differ")
    return out


def ref_error(got, ref) -> Dict[str, float]:
    import numpy as np

    got = np.asarray(got, np.float32)
    check(np.isfinite(got).all(), "reference: non-finite engine logits")
    return {"rel_l2_error": float(np.linalg.norm(got - ref)
                                  / np.linalg.norm(ref)),
            "max_abs_error": float(np.max(np.abs(got - ref))),
            "argmax_agree": int(np.sum(got.argmax(-1) == ref.argmax(-1)))}


def phase_ref(model, params, prompts, paged, track: int, sizes: Sizes):
    """The paged run's logits for ``track`` against the float32 reference
    pass teacher-forced on that run's tokens; returns the reference."""
    streams, logits = paged
    prompt = prompts[track]
    tokens = prompt + streams[track][:sizes.ref_steps - 1]
    ref = reference_logits(model, params, tokens, len(prompt) - 1,
                           sizes.ref_steps)
    err = ref_error(logits, ref)
    emit(phase="reference", request=track, prompt_tokens=len(prompt),
         rows=sizes.ref_steps, tolerance=REF_TOL, **err)
    check(err["rel_l2_error"] <= REF_TOL,
          f"reference: relative error {err['rel_l2_error']} > {REF_TOL}")
    return ref


def phase_b(model, seed: int, prompts, sizes: Sizes, clock, device,
            resident, track: int, ref):
    """Experts off the chip, twice: behind a cache of half the blocks and
    behind one of a quarter.  The tiered pipeline (a jit per layer
    half, the routed picks synced to the host between them) is a different
    program from the resident scan, so on the TPU its roundings differ and
    streams part from the resident run's; what expert placement must not
    change is the tiered run's own result, so the two placements must be
    bitwise equal, and each stays within tolerance of the f32 reference."""
    from repro.serve import LLM

    out = {}
    n_blocks = model.cfg.n_layers * model.moe_cfg.padded_experts
    for name, cache in (("half", sizes.expert_cache),
                        ("quarter", sizes.expert_cache // 2)):
        c0, t0 = clock.total, time.perf_counter()
        params = init_weights(model, seed)
        llm = LLM(model, params, serve_config(
            sizes, pages_held(prompts, sizes) + sizes.max_batch + 1,
            keep_logits=True, expert_offchip=True, expert_cache_size=cache))
        # The store holds the experts now (its host tier has landed, layer
        # by layer): drop the resident stacks so HBM does not hold them
        # twice.
        for w in ("w_gate", "w_up", "w_down"):
            params["layers"]["moe"][w].delete()
        engine = llm.cluster.replicas[0].engine
        st = engine.expert_store
        kinds = {"host": sorted(st.host.memory_kinds()),
                 "cache": sorted({a.sharding.memory_kind for a in (
                     st.w_gate_cache, st.w_up_cache, st.w_down_cache)})}
        streams, logits = run_sessions(llm, prompts, sizes, track=track,
                                       label=f"B_{name}")
        stats = llm.stats()
        kernels = kernel_check(engine, sizes, tiered=True)
        # Logits rows whose inputs match the reference's teacher forcing:
        # row i is fed the first i generated tokens.
        same = next((i for i, (x, y) in enumerate(
            zip(streams[track], resident[track])) if x != y),
            sizes.ref_steps)
        rows = min(sizes.ref_steps, same + 1)
        err = ref_error(logits[:rows], ref[:rows])
        emit(phase=f"B_{name}", expert_cache_blocks=st.cache_slots,
             expert_blocks=n_blocks, store_memory_kinds=kinds,
             demand_fetches=stats["expert_demand_fetches"],
             prefetch_fetches=stats["expert_prefetch_fetches"],
             prefetch_hits=stats["expert_prefetch_hits"],
             expert_transfer_events=stats["expert_transfer_events"],
             kernels=kernels, reference_rows=rows, tolerance=REF_TOL, **err,
             streams_differing_from_resident=stream_diff(streams, resident),
             compile_seconds=clock.total - c0,
             phase_seconds=time.perf_counter() - t0,
             peak_bytes_in_use=peak_bytes(device))
        check(all(kernels.values()), f"B_{name}: a program without {PROBE}")
        check(kinds == {"host": ["pinned_host"], "cache": ["device"]},
              f"B_{name}: store memory kinds {kinds}")
        check(stats["expert_demand_fetches"]
              + stats["expert_prefetch_fetches"] > 0,
              f"B_{name}: no expert block was fetched")
        check(all(len(x) == sizes.max_new for x in streams),
              f"B_{name}: a stream ended short")
        check(err["rel_l2_error"] <= REF_TOL,
              f"B_{name}: relative error {err['rel_l2_error']} > {REF_TOL}")
        out[name] = streams
        del llm, engine, st, params
        gc.collect()
    diff = stream_diff(out["half"], out["quarter"])
    emit(phase="B_compare", streams_bitwise_equal=not diff,
         streams_differing=diff)
    check(not diff, "B: streams depend on the expert cache size")


def phase_replicas(model, params, prompts, sizes: Sizes, n: int):
    """One replica on chip 0, then ``n`` replicas (one per chip) on the
    same requests with one drained halfway; streams must match.  Pools hold
    every session, so the chips' time goes to the replicas and the
    cross-chip handoff, not to paging (phase A covers that)."""
    import jax

    from repro.serve import LLM

    cfg = serve_config(sizes, pages_held(prompts, sizes) + sizes.max_batch + 1)
    one, _ = run_sessions(LLM(model, params, cfg), prompts, sizes,
                          label="one replica")
    gc.collect()
    llm = LLM(model, params, cfg, replicas=n)
    reps = llm.cluster.replicas
    placement = []
    for rep in reps:
        e = rep.engine
        devs = {e.device}.union(*(a.devices() for a in e.pool.hbm))
        for a in jax.tree.leaves(e.params):
            devs |= a.devices()
        placement.append(sorted(str(d) for d in devs))
    half = sizes.sessions * sizes.max_new // 2
    drained: List[int] = []

    def drain_halfway(_rnd, generated):
        if not drained and generated >= half:
            victim = max(reps, key=lambda r: len(r.engine.requests))
            llm.cluster.drain(victim.replica_id)
            drained.append(victim.replica_id)

    many, _ = run_sessions(llm, prompts, sizes, on_round=drain_halfway,
                           label=f"{n} replicas")
    exported = sum(r.engine.pool.exported_pages for r in reps
                   if r.replica_id in drained)
    stats = llm.stats()
    diff = stream_diff(many, one)
    emit(phase="replicas", replicas=n, placement=placement,
         drained=drained, exported_pages=exported,
         migrations_warm=stats["cluster_migrations_warm"],
         migrations_cold=stats["cluster_migrations_cold"],
         streams_bitwise_equal_to_one_replica=not diff,
         streams_differing=diff)
    check(all(len(p) == 1 for p in placement)
          and len({p[0] for p in placement}) == n,
          f"replicas: arrays not one device per replica: {placement}")
    check(exported > 0, "replicas: the drain exported no pages")
    check(not diff, "replicas: streams differ from the one-replica run")


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    import jax

    enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print("chip_smoke: no TPU: JAX found only "
              f"{dev.platform} devices", file=sys.stderr)
        return 2
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    emit(phase="device", **info)
    from repro.core.hwmodel import TPU_V5E  # the tier constants assumed

    check("v5 lite" in dev.device_kind.lower()
          or "v5e" in dev.device_kind.lower(),
          f"device kind {dev.device_kind!r} is not a v5e: the engine's "
          f"tier constants ({TPU_V5E.name}) describe a v5e")
    check(len(devices) == args.chips,
          f"--chips {args.chips} but JAX sees {len(devices)} devices")

    from repro.configs import get
    from repro.kernels import ops
    from repro.models import build_model

    emit(phase="ops", mode=ops.current_mode())
    check(ops.current_mode() == "pallas",
          f"ops mode is {ops.current_mode()!r}, not 'pallas'")
    clock = CompileClock()
    sizes = Sizes()
    mcfg = dataclasses.replace(get(ARCH), remat=False)
    model = build_model(mcfg)
    t0 = time.perf_counter()
    params = init_weights(model, args.seed)
    prompts = make_prompts(sizes, mcfg.vocab, args.seed)
    emit(phase="setup", arch=ARCH, n_layers=mcfg.n_layers,
         d_model=mcfg.d_model, n_experts=mcfg.n_experts, top_k=mcfg.top_k,
         params=sum(a.size for a in jax.tree.leaves(params)),
         prompt_tokens=[len(p) for p in prompts],
         pages_held=pages_held(prompts, sizes),
         setup_seconds=time.perf_counter() - t0,
         peak_bytes_in_use=peak_bytes(dev))

    if args.chips > 1:
        phase_replicas(model, params, prompts, sizes, args.chips)
    else:
        # The reference check runs on the shortest prompt (its float32
        # MoE oracle is the largest transient of the script).
        track = min(range(len(prompts)), key=lambda i: len(prompts[i]))
        a = phase_a(model, params, prompts, sizes, clock, dev, track)
        ref = phase_ref(model, params, prompts, a["paged"], track, sizes)
        del params                  # phase B draws its own, one run each
        gc.collect()
        phase_b(model, args.seed, prompts, sizes, clock, dev,
                a["resident"][0], track, ref)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        sys.exit(1)
