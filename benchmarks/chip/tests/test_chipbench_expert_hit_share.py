"""The ``expert_hit_share`` reader on synthetic windows: the share of the
expert blocks dispatches read that were already resident, from the
window's deltas of ``expert_resident_hits`` and ``expert_needed_blocks``,
and nothing where the program has no such counters."""

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import peaks  # noqa: E402


def window(counters0, counters1):
    """Two decode steps of 32 rows inside a window of 1 s."""
    with open(BENCH / "configs" / "granite-moe-3b-a800m.json") as f:
        shape = peaks.Shape.from_config(json.load(f)["model"])
    steps = [harness.Step(0.1 + 0.4 * i, 0.5 + 0.4 * i,
                          [(r, 600 + r + i) for r in range(32)], [], 0, 0)
             for i in range(2)]
    return harness.Window(0.0, 1.0, steps, counters0, counters1, shape,
                          peaks.V5E, None, 64)


@pytest.mark.parametrize("c0,c1,want", [
    ({"expert_needed_blocks": 1280, "expert_resident_hits": 0},
     {"expert_needed_blocks": 3840, "expert_resident_hits": 1200}, 46.875),
    ({"steps": 10}, {"steps": 40}, None),                 # no counter
    ({"expert_needed_blocks": 640, "expert_resident_hits": 320},
     {"expert_needed_blocks": 640, "expert_resident_hits": 320},
     None),                                               # no dispatch
])
def test_expert_hit_share_reads_the_window_delta(c0, c1, want):
    read = harness.metric_reader(BENCH, "expert_hit_share")
    assert read(window(c0, c1)) == want
