"""End-to-end arithmetic: a rate over the whole window, tails over every
request due in it, unserved requests counted at their age, and a stall
that raises the tails."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import traffic  # noqa: E402


def sent(rid, due, times):
    s = harness.Sent(req=traffic.Request(rid=rid, prompt=[1], max_tokens=9),
                     due=due)
    s.times = list(times)
    return s


def window():
    # Window 10..20 s.  Request 0 is due before the window (no TTFT);
    # 1 and 2 are due inside and served every 0.1 s; 3 is due at 19 and
    # never served.
    return {0: sent(0, 5.0, np.arange(10.05, 20.0, 0.1)),
            1: sent(1, 11.0, np.arange(11.2, 15.0, 0.1)),
            2: sent(2, 12.0, np.arange(12.5, 21.0, 0.1)),
            3: sent(3, 19.0, [])}


def test_rate_is_every_token_in_the_window_over_its_seconds():
    w = window()
    out = harness.end_to_end(w, 10.0, 20.0)
    inside = sum(sum(1 for t in s.times if 10.0 <= t <= 20.0)
                 for s in w.values())
    assert out["tokens"] == inside
    assert out["tok_s"] == pytest.approx(inside / 10.0)


def test_tail_counts_every_due_request_and_unserved_at_their_age():
    out = harness.end_to_end(window(), 10.0, 20.0)
    ttft = [0.2, 0.5, 1.0]                  # request 3: its age at 20
    assert out["turns"] == 3
    assert out["ttft_p90_ms"] == pytest.approx(
        1e3 * np.quantile(ttft, 0.9))


def test_a_stall_raises_both_tails():
    base = harness.end_to_end(window(), 10.0, 20.0)
    w = window()
    # The engine stalls 0.5 s at each of six moments: every token after a
    # stall comes that much later, and a request due during one waits.
    stalls = [10.5, 11.5, 12.5, 13.5, 14.5, 15.5]
    for s in w.values():
        s.times = [t + 0.5 * sum(t > x for x in stalls) for t in s.times]
    w[4] = sent(4, 13.5, [16.0])
    stalled = harness.end_to_end(w, 10.0, 20.0)
    assert stalled["ttft_p90_ms"] > base["ttft_p90_ms"]
    assert stalled["itl_p95_ms"] == pytest.approx(600.0)
    assert base["itl_p95_ms"] == pytest.approx(100.0)


def test_gaps_need_both_tokens_inside_the_window():
    w = {0: sent(0, 0.0, [9.0, 10.5, 11.0, 21.0])}
    out = harness.end_to_end(w, 10.0, 20.0)
    assert out["gaps"] == 1
    assert out["itl_p95_ms"] == pytest.approx(500.0)
