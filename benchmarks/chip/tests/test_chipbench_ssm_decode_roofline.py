"""The ``ssm_decode_roofline`` reader on a small synthetic trace: the
Mamba-2 decode kernel's operations and bytes by hand, its share of the
roofline from its trace time, and nothing where the trace lacks it."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import peaks  # noqa: E402
import profile_trace  # noqa: E402

READER = BENCH / "metrics" / "ssm_decode_roofline.py"


def summary(op_s):
    return profile_trace.Summary(
        window_s=1.0, busy_s=sum(op_s.values()), op_s=op_s,
        op_calls={k: 9 for k in op_s}, gaps_by_span={})


def window(trace):
    """Two decode steps of 64 rows in 1 s."""
    with open(BENCH / "configs" / "granite-4.0-h-small.10of40.ep2.json") as f:
        shape = peaks.Shape.from_config(json.load(f)["model"])
    steps = [harness.Step(0.1 + 0.4 * i, 0.5 + 0.4 * i,
                          [(r, 3000 + r + i) for r in range(64)], [], 0, 0)
             for i in range(2)]
    return harness.Window(0.0, 1.0, steps, {}, {}, shape, peaks.V5E, trace,
                          128)


def test_cost_by_hand():
    mod = harness._load_file(READER, "ssm_decode_roofline_test")
    # One row, 128 heads of 64, a state of 128, one group.
    flops, nbytes = mod.cost(128, 64, 128, 1, 1)
    assert flops == 6 * 128 * 64 * 128
    assert nbytes == 4 * (2 * 128 * 64 * 128 + 2 * 128 * 64 + 128 + 2 * 128)
    assert mod.cost(128, 64, 128, 1, 3) == (3 * flops, 3 * nbytes)


def test_share_from_the_kernel_time():
    read = harness.metric_reader(BENCH, "ssm_decode_roofline")
    mod = harness._load_file(READER, "ssm_decode_roofline_test")
    flops, nbytes = mod.cost(128, 64, 128, 1, 128)
    # Memory-bound: 9 Mamba layers of float32 states over 819 GB/s.
    least = 9 * nbytes / peaks.V5E.hbm_bytes_per_s
    assert 9 * flops / peaks.V5E.flops_bf16 < least
    got = read(window(summary({"ssm_decode_pallas": 2 * least,
                               "ssm_state_read_pallas": 0.1})))
    assert got == pytest.approx(50.0)


@pytest.mark.parametrize("trace", [None, summary({"copy": 0.2}),
                                   summary({"paged_attention_pallas": 0.1})])
def test_nothing_without_the_kernel(trace):
    read = harness.metric_reader(BENCH, "ssm_decode_roofline")
    assert read(window(trace)) is None
