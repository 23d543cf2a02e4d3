"""The ``expert_landed_share`` reader on synthetic windows: the share of
the committed prefetched blocks whose slab had landed, from the window's
deltas of ``expert_landed_blocks`` and ``expert_committed_blocks``, and
nothing where the program has no such counters or committed nothing."""

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
    ({"expert_committed_blocks": 640, "expert_landed_blocks": 600},
     {"expert_committed_blocks": 1920, "expert_landed_blocks": 1560}, 75.0),
    ({"steps": 10, "expert_needed_blocks": 1280},
     {"steps": 40, "expert_needed_blocks": 3840}, None),  # no counter
    ({"expert_committed_blocks": 640, "expert_landed_blocks": 320},
     {"expert_committed_blocks": 640, "expert_landed_blocks": 320},
     None),                                               # no commit
], ids=["landed", "parent", "no-commit"])
def test_expert_landed_share_reads_the_window_delta(c0, c1, want):
    read = harness.metric_reader(BENCH, "expert_landed_share")
    assert read(window(c0, c1)) == want
