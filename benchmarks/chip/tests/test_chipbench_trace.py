"""The reduction from a profiler trace to device busy time, operation
sums and idle gaps put down to host spans, on a small recorded trace
(``data/small_trace.json``) and on a real trace the CPU profiler writes."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import profile_trace as pt  # noqa: E402


def small():
    with open(HERE / "data" / "small_trace.json") as f:
        return [pt.Event(*e) for e in json.load(f)]


def test_busy_is_the_union_of_operations_inside_the_window():
    s = pt.summarize(small())
    assert s.window_s == pytest.approx(10000e-9)
    # 1000..1500, 2000..3500 (two overlapping), 4000..6000, 9500..10500
    assert s.busy_s == pytest.approx((500 + 1500 + 2000 + 1000) * 1e-9)
    assert s.idle_share == pytest.approx(1 - 5000 / 10000)


def test_kernel_time_sums_instances_and_clips_to_the_window():
    s = pt.summarize(small())
    secs, calls = s.kernel_seconds(["paged_attention_pallas"])
    assert calls == 2 and secs == pytest.approx(2000e-9)
    assert s.op_s["fusion"] == pytest.approx((500 + 1000) * 1e-9)
    assert s.kernel_seconds(["absent"]) == (0.0, 0)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    s = pt.summarize(small())
    # gaps: 1500..2000 step, 3500..4000 step, 6000..9500 (step 1000, wait
    # 2000, step 500 -> wait), 10500..11000 step
    assert s.gaps_by_span["bench.step"] == pytest.approx(1500e-9)
    assert s.gaps_by_span["bench.wait"] == pytest.approx(3500e-9)
    b = s.breakdown()
    assert b["idle_gaps"][0][0] == "bench.wait"
    assert b["device_ops"][0][0] == "paged_attention_pallas"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        pt.summarize([e for e in small() if e.name != "bench.window"])


def test_a_recorded_profiler_trace_yields_the_window_span(tmp_path):
    import jax
    import jax.numpy as jnp

    pt.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.step"):
                jnp.ones((64, 64)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = pt.load_dir(tmp_path)
    names = {e.name for e in events}
    assert {"bench.window", "bench.step"} <= names
    assert all(e.name.startswith("bench.") for e in events
               if not e.plane.startswith("/device:"))
    s = pt.summarize(events)
    assert s.window_s > 0
