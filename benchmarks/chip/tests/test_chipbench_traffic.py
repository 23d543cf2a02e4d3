"""The traffic generator: deterministic per seed, the same sizes and
schedule for every seed, and the stated distributions."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def mix(name):
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def gen(name, seed, seconds=45.0):
    return traffic.generate(mix(name), seed, 49152, seconds)


POISSON = {"shape_seed": 3, "arrival": {"kind": "poisson", "rate_per_s": 20.0},
           "prompt": {"dist": "lognormal", "median": 200, "sigma": 0.7,
                      "min": 64, "max": 512},
           "output": {"dist": "uniform", "min": 64, "max": 256}}


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = gen(name, 2**31 + 17), gen(name, 2**31 + 17)
    assert [r.prompt for r in a.requests] == [r.prompt for r in b.requests]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes_and_schedule(name):
    a, b = gen(name, 1), gen(name, 99)
    shape = lambda t: [(len(r.prompt), r.max_tokens, r.due_s, r.client)  # noqa: E731
                       for r in t.requests]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a.requests] != [r.prompt for r in b.requests]


def test_lognormal_and_uniform_lengths_follow_their_parameters():
    rng = np.random.default_rng(0)
    ln = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 128,
          "max": 2048}
    x = traffic.lengths(ln, rng, 20000)
    assert x.min() >= 128 and x.max() <= 2048
    assert np.median(x) == pytest.approx(512, rel=0.05)
    inner = x[(x > 128) & (x < 2048)]
    assert np.std(np.log(inner)) < 0.8       # clipped tails only narrow it
    u = traffic.lengths({"dist": "uniform", "min": 64, "max": 256}, rng,
                        20000)
    assert u.min() == 64 and u.max() == 256
    assert u.mean() == pytest.approx(160, rel=0.02)
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "pareto", "min": 1, "max": 2}, rng, 3)


def test_poisson_arrivals_at_the_stated_rate():
    t = traffic.generate(POISSON, 1, 49152, 200.0)
    due = np.array([r.due_s for r in t.requests])
    assert np.all(np.diff(due) >= 0) and due.max() < 200.0
    assert len(due) / 200.0 == pytest.approx(20.0, rel=0.05)
    gaps = np.diff(due)
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, rel=0.1)
    assert traffic.closed_order(t) is None


def test_closed_loop_queues_hold_every_request_once_in_order():
    t = gen("batch-backlog-258", 4)
    q = traffic.closed_order(t)
    assert len(q) == mix("batch-backlog-258")["arrival"]["clients"]
    assert sorted(r.rid for x in q for r in x) == list(range(len(t.requests)))
    assert all([r.rid for r in x] == sorted(r.rid for r in x) for x in q)
    assert all(r.due_s == 0.0 for r in t.requests)


def test_prefill_buckets_cover_the_prompts():
    assert traffic.prefill_buckets(POISSON, 16) == [64, 128, 256, 512]
    b = mix("batch-backlog-258")
    # Prompts of 258 tokens and more ingest 257 or more: no 256 bucket.
    assert traffic.prefill_buckets(b, 16) == [512, 1024, 2048]
    assert min(len(r.prompt) for r in gen("batch-backlog-258", 1).requests
               ) >= 258
