"""The ``mla_decode_roofline`` reader on a small synthetic trace: the
absorbed latent decode kernel's operations and bytes by hand, its share of
the roofline from its trace time, and nothing where the trace lacks it."""

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


def summary(op_s):
    return profile_trace.Summary(
        window_s=1.0, busy_s=sum(op_s.values()), op_s=op_s,
        op_calls={k: 7 for k in op_s}, gaps_by_span={})


def window(trace):
    """Two decode steps of 32 rows, contexts 2,000 to 2,032, in 1 s."""
    with open(BENCH / "configs" / "deepseek-v2-lite.7of27.json") as f:
        shape = peaks.Shape.from_config(json.load(f)["model"])
    steps = [harness.Step(0.1 + 0.4 * i, 0.5 + 0.4 * i,
                          [(r, 2000 + r + i) for r in range(32)], [], 0, 0)
             for i in range(2)]
    return harness.Window(0.0, 1.0, steps, {}, {}, shape, peaks.V5E, trace,
                          64)


def test_cost_by_hand():
    mod = harness._load_file(BENCH / "metrics" / "mla_decode_roofline.py",
                             "mla_decode_roofline_test")
    # One row of 1,000 tokens, 16 heads, latent 576, values 512.
    flops, nbytes = mod.cost(16, 576, 512, [1000])
    assert flops == 2 * 16 * 1000 * (576 + 512)
    assert nbytes == 2 * (1000 * 576 + 16 * 576 + 16 * 512)


def test_share_from_the_kernel_time():
    read = harness.metric_reader(BENCH, "mla_decode_roofline")
    mod = harness._load_file(BENCH / "metrics" / "mla_decode_roofline.py",
                             "mla_decode_roofline_test")
    contexts = [2000 + r + i for i in range(2) for r in range(32)]
    flops, nbytes = mod.cost(16, 576, 512, contexts)
    # Memory-bound: 7 layers of latents over 819 GB/s.
    least = 7 * nbytes / peaks.V5E.hbm_bytes_per_s
    assert 7 * flops / peaks.V5E.flops_bf16 < least
    got = read(window(summary({"paged_mla_decode_pallas": 4 * least,
                               "copy": 0.5})))
    assert got == pytest.approx(25.0)


@pytest.mark.parametrize("trace", [None, summary({"copy": 0.2}),
                                   summary({"paged_attention_pallas": 0.1})])
def test_nothing_without_the_kernel(trace):
    read = harness.metric_reader(BENCH, "mla_decode_roofline")
    assert read(window(trace)) is None
