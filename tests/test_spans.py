"""The program's spans (``core/spans.py``) in a real profiler trace: three
decode steps of a tiny MoE model with its experts off the chip, traced on
the CPU and read back with the chip benchmark's reduction.

Every ``repro.*`` span must nest inside a ``repro.engine.step`` on the
engine's thread, each step must route and dispatch every layer once, and
``host_syncs`` must count exactly the ``repro.sync.*`` spans.  A span put
inside jitted code would be recorded while tracing, not per step, and
break the counts."""

import dataclasses
import sys
from collections import Counter
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke
from repro.models import build_model
from repro.serve import LLM, SamplingParams, ServeConfig

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))

import span_trace  # noqa: E402

STEPS = 3


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = dataclasses.replace(get_smoke("granite_moe_3b_a800m"),
                              remat=False)
    model = build_model(cfg)
    llm = LLM(model, model.init(jax.random.PRNGKey(0)), ServeConfig(
        max_batch=2, page_size=4, hbm_pages=32, host_pages=64,
        max_pages_per_seq=16, interval_steps=2, policy="gdt",
        expert_offchip=True,
        expert_cache_size=cfg.n_layers * cfg.n_experts // 2))
    for rid, prompt in enumerate(([3, 1, 4, 1, 5, 9], [2, 7, 1, 8, 2, 8])):
        llm.submit(prompt, SamplingParams(max_tokens=16), request_id=rid)
    for _ in range(2):          # admit, prefill and compile every program
        llm.step()
    before = llm.stats()["host_syncs"]
    trace_dir = tmp_path_factory.mktemp("trace")
    span_trace.start(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(STEPS):
                llm.step()
    finally:
        jax.profiler.stop_trace()
    events = [e for e in span_trace.load_dir(trace_dir)
              if e.name.startswith("repro.")]
    return cfg, events, llm.stats()["host_syncs"] - before


def inside(e, outer):
    return (outer.start_ns <= e.start_ns
            and e.end_ns <= outer.end_ns + 1e3)      # 1 us of rounding


def test_each_step_routes_and_dispatches_every_layer(traced):
    cfg, events, _ = traced
    steps = [e for e in events if e.name == "repro.engine.step"]
    assert len(steps) == STEPS
    for step in steps:
        names = Counter(e.name for e in events
                        if e is not step and inside(e, step))
        assert names["repro.sync.route"] == cfg.n_layers
        assert names["repro.expert.dispatch"] == cfg.n_layers
        assert names["repro.dispatch.attn"] == cfg.n_layers
        assert names["repro.dispatch.ffn"] == cfg.n_layers
        assert names["repro.engine.decode"] == 1
        assert names["repro.sync.tokens"] == 1
        assert names["repro.guidance.on_step"] == 2     # KV and experts


def test_every_program_span_nests_in_an_engine_step(traced):
    _, events, _ = traced
    steps = [e for e in events if e.name == "repro.engine.step"]
    assert len({(e.plane, e.line) for e in events}) == 1
    for e in events:
        assert any(inside(e, s) for s in steps), e


def test_host_syncs_counts_the_sync_spans(traced):
    cfg, events, delta = traced
    syncs = [e for e in events if e.name.startswith("repro.sync.")]
    assert delta == len(syncs)
    # Per step: the routed picks of each layer, the speculative router's
    # forecast of every layer after the first and of the next step's
    # first, and the sampled tokens.
    assert delta == STEPS * (2 * cfg.n_layers + 1)


def test_a_forecast_of_every_expert_is_not_read_back(tmp_path):
    """At 4 rows x top-2 + 2 >= 8 experts the forecast names every expert:
    the store stages whole layers ahead and the engine reads back only the
    routed picks of each layer and the sampled tokens."""
    cfg = dataclasses.replace(get_smoke("granite_moe_3b_a800m"),
                              remat=False)
    model = build_model(cfg)
    assert 4 * model.moe_cfg.top_k + 2 >= model.moe_cfg.padded_experts
    llm = LLM(model, model.init(jax.random.PRNGKey(0)), ServeConfig(
        max_batch=4, page_size=4, hbm_pages=48, host_pages=64,
        max_pages_per_seq=16, interval_steps=2, policy="gdt",
        expert_offchip=True,
        expert_cache_size=cfg.n_layers * cfg.n_experts // 2))
    for rid in range(4):
        llm.submit([3 + rid, 1, 4, 1, 5, 9], SamplingParams(max_tokens=16),
                   request_id=rid)
    for _ in range(2):
        llm.step()
    before = llm.stats()["host_syncs"]
    span_trace.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(STEPS):
                llm.step()
    finally:
        jax.profiler.stop_trace()
    events = [e for e in span_trace.load_dir(tmp_path)
              if e.name.startswith("repro.")]
    names = Counter(e.name for e in events)
    assert names["repro.engine.decode"] == STEPS
    assert names["repro.sync.spec"] == 0
    assert llm.stats()["host_syncs"] - before == STEPS * (cfg.n_layers + 1)
