"""The reduction with the program's spans (``span_trace.py``) and the
per-layer readers of those spans and of the ``host_syncs`` counter: self
times and idle gaps split to the innermost program span on a recorded
nested trace (``data/nested_trace.json``), ``profile_trace``'s results to
the field on a trace without program spans (``data/small_trace.json``),
and the readers on synthetic windows."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import peaks  # noqa: E402
import profile_trace as pt  # noqa: E402
import span_trace as st  # noqa: E402

NS = 1e-9
NEW_READERS = ("device_wait_ms.batch", "expert_store_ms.batch",
               "guidance_ms.batch", "host_syncs_per_step")


def recorded(name):
    with open(HERE / "data" / name) as f:
        return [pt.Event(*e) for e in json.load(f)]


def host(name, a, b):
    return pt.Event("/host:CPU", "python", name, a, b - a)


def op(name, a, b):
    return pt.Event("/device:TPU:0", "XLA Ops", name, a, b - a)


# ------------------------------------------------------------ reduction
def test_a_trace_without_program_spans_reduces_as_profile_trace_does():
    events = recorded("small_trace.json")
    old, new = pt.summarize(events), st.summarize(events)
    assert new.window_s == old.window_s
    assert new.busy_s == old.busy_s
    assert new.op_s == old.op_s and new.op_calls == old.op_calls
    assert new.gaps_by_span == old.gaps_by_span
    assert new.breakdown() == old.breakdown()
    assert new.span_s == {} and new.span_seconds(["repro."]) == (0.0, 0)


def test_self_time_is_the_length_on_the_innermost_span_timeline():
    s = st.summarize(recorded("nested_trace.json"))
    want = {"repro.engine.step": 30, "repro.engine.schedule": 20,
            "repro.engine.decode": 150, "repro.dispatch.attn": 10,
            "repro.sync.route": 200, "repro.expert.dispatch": 20,
            "repro.expert.transfer": 50, "repro.dispatch.ffn": 10,
            "repro.sync.spec": 180, "repro.sync.tokens": 180,
            "repro.guidance.on_step": 30}
    assert set(s.span_s) == set(want)
    for name, ns in want.items():
        assert s.span_s[name] == pytest.approx(ns * NS), name
    # Self times tile the outermost span.
    assert sum(s.span_s.values()) == pytest.approx(880 * NS)
    assert s.span_calls == {name: 1 for name in want}
    assert s.span_seconds(["repro.sync."]) == (
        pytest.approx(560 * NS), 3)
    assert s.span_seconds(["repro.expert.", "repro.guidance."]) == (
        pytest.approx(100 * NS), 3)


def test_idle_gaps_split_to_the_innermost_program_span():
    s = st.summarize(recorded("nested_trace.json"))
    # Gaps 60..200, 320..500, 520..600 and 700..950, all in bench.step;
    # only 940..950 lies outside every program span.
    want = {"repro.engine.step": 30, "repro.engine.schedule": 20,
            "repro.engine.decode": 50, "repro.dispatch.attn": 10,
            "repro.sync.route": 80, "repro.expert.dispatch": 20,
            "repro.expert.transfer": 50, "repro.dispatch.ffn": 10,
            "repro.sync.spec": 160, "repro.sync.tokens": 180,
            "repro.guidance.on_step": 30, "bench.step": 10}
    assert set(s.gaps_by_span) == set(want)
    for name, ns in want.items():
        assert s.gaps_by_span[name] == pytest.approx(ns * NS), name
    assert sum(s.gaps_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["idle_gaps"][0][0] == "repro.sync.tokens"
    assert b["program_spans"][0][0] == "repro.sync.route"
    assert len(b["program_spans"]) == 10 and len(b["idle_gaps"]) == 10


@pytest.mark.parametrize("child,gap_to", [
    ("bench.submit", "bench.step"),          # nested bench spans
    ("repro.engine.decode", "bench.step"),   # a program child alone
])
def test_a_gap_after_a_nested_child_goes_to_the_enclosing_span(child,
                                                               gap_to):
    """A step span over 0..100 with a child over 10..20 and a gap over
    30..40: the enclosing step covers the gap."""
    events = [host("bench.window", 0, 100), host("bench.step", 0, 100),
              host(child, 10, 20), op("fusion", 0, 30),
              op("fusion", 40, 100)]
    s = st.summarize(events)
    assert s.gaps_by_span == {gap_to: pytest.approx(10 * NS)}


def test_a_gap_inside_a_program_step_after_its_child_goes_to_the_step():
    events = [host("bench.window", 0, 100), host("bench.step", 0, 100),
              host("repro.engine.step", 0, 100),
              host("repro.engine.decode", 10, 20),
              op("fusion", 0, 30), op("fusion", 40, 100)]
    s = st.summarize(events)
    assert s.gaps_by_span == {"repro.engine.step": pytest.approx(10 * NS)}
    assert s.span_s["repro.engine.step"] == pytest.approx(90 * NS)
    assert s.span_s["repro.engine.decode"] == pytest.approx(10 * NS)


def test_spans_are_clipped_to_the_window_and_children_to_their_parent():
    events = [host("bench.window", 100, 200),
              host("repro.engine.step", 50, 150),
              host("repro.sync.tokens", 140, 170),    # outlasts its parent
              host("repro.engine.step", 180, 260),
              op("fusion", 100, 200)]
    s = st.summarize(events)
    assert s.span_s["repro.engine.step"] == pytest.approx(60 * NS)
    assert s.span_s["repro.sync.tokens"] == pytest.approx(10 * NS)
    assert s.span_calls == {"repro.engine.step": 2, "repro.sync.tokens": 1}
    assert s.gaps_by_span == {}


def test_a_recorded_profiler_trace_keeps_the_program_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    st.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("repro.engine.step", step=1):
                with jax.profiler.TraceAnnotation("repro.sync.tokens"):
                    jnp.ones((64, 64)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = st.load_dir(tmp_path)
    names = {e.name for e in events}
    assert {"bench.window", "repro.engine.step",
            "repro.sync.tokens"} <= names
    assert all(e.name.startswith(("bench.", "repro.")) for e in events
               if not e.plane.startswith("/device:"))
    s = st.summarize(events)
    assert s.span_calls["repro.engine.step"] == 1
    assert 0 < s.span_s["repro.sync.tokens"] <= s.window_s


# -------------------------------------------------------------- readers
def shape():
    with open(BENCH / "configs" / "granite-moe-3b-a800m.json") as f:
        return peaks.Shape.from_config(json.load(f)["model"])


def window(trace, counters0=None, counters1=None):
    """Two decode steps of 32 rows inside a window of 1 s."""
    steps = [harness.Step(0.1 + 0.4 * i, 0.5 + 0.4 * i,
                          [(r, 600 + r + i) for r in range(32)], [], 0, 0)
             for i in range(2)]
    return harness.Window(0.0, 1.0, steps, counters0 or {},
                          counters1 or {}, shape(), peaks.V5E, trace, 64)


def reader(name):
    return harness.metric_reader(BENCH, name)


@pytest.mark.parametrize("name,ns", [
    ("device_wait_ms.batch", 560), ("expert_store_ms.batch", 70),
    ("guidance_ms.batch", 30)])
def test_span_readers_give_self_time_per_window_step(name, ns):
    w = window(st.summarize(recorded("nested_trace.json")))
    assert reader(name)(w) == pytest.approx(1e3 * ns * NS / 2)


@pytest.mark.parametrize("name", NEW_READERS[:3])
@pytest.mark.parametrize("reduce", [pt.summarize, st.summarize, None])
def test_span_readers_read_nothing_without_program_spans(name, reduce):
    trace = reduce(recorded("small_trace.json")) if reduce else None
    assert reader(name)(window(trace)) is None


@pytest.mark.parametrize("c0,c1,want", [
    ({"steps": 10, "host_syncs": 650}, {"steps": 40, "host_syncs": 2600},
     65.0),
    ({"steps": 10}, {"steps": 40}, None),                 # no counter
    ({"steps": 40, "host_syncs": 9}, {"steps": 40, "host_syncs": 9},
     None),                                               # no step
])
def test_host_syncs_per_step_reads_the_window_delta(c0, c1, want):
    assert reader("host_syncs_per_step")(window(None, c0, c1)) == want


def test_the_accepted_readers_read_the_same_from_either_reduction():
    names = ("expert_demand_per_step", "expert_mb_per_tok", "step_ms.batch",
             "paged_attention_roofline.batch", "moe_gemm_roofline",
             "mfu.batch", "device_idle_share.batch")
    events = recorded("small_trace.json")
    counters = ({"steps": 10, "expert_demand_fetches": 3,
                 "expert_bytes_fetched": 10**9},
                {"steps": 12, "expert_demand_fetches": 9,
                 "expert_bytes_fetched": 3 * 10**9})
    old = window(pt.summarize(events), *counters)
    new = window(st.summarize(events), *counters)
    for name in names:
        got = reader(name)(new)
        assert got is not None, name
        assert got == reader(name)(old), name
