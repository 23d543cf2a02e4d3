"""The one traffic generator.  A mix is a JSON file of parameters under
``traffic/``; this module turns it and a seed into requests.

Sizes and arrival times come from the mix's own ``shape_seed``, so every
run seed gets the same set of lengths and the same schedule and the work
stays fixed; the run seed draws the token ids.  Keys of a mix:

* ``arrival``: ``{"kind": "closed", "clients": N, "requests": M}`` (N
  clients, each sending its next request when the last one finished; M
  requests queued before the window) or ``{"kind": "poisson",
  "rate_per_s": r}`` (open loop over the run's seconds);
* ``prompt``, ``output``: length distributions of a request's prompt and
  of its answer (``{"dist": "lognormal", "median", "sigma", "min",
  "max"}`` or ``{"dist": "uniform", "min", "max"}``).

Token ids are uniform over ``[1, vocab)``; decoding is greedy with no stop
token, so every answer runs to its length.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_tokens: int
    due_s: float = 0.0        # open loop: seconds after the window opens
    client: int = -1          # closed loop: the client that sends it


@dataclasses.dataclass
class Traffic:
    requests: List[Request]
    clients: int = 0          # > 0: closed loop


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def lengths(spec: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def generate(mix: dict, seed: int, vocab: int, seconds: float) -> Traffic:
    shape = np.random.default_rng(int(mix["shape_seed"]))
    tok = np.random.default_rng(np.random.SeedSequence(
        int(seed) % (1 << 64)))
    arr = mix["arrival"]
    if arr["kind"] == "closed":
        n = int(arr["requests"])
        due = np.zeros(n)
    elif arr["kind"] == "poisson":
        gaps = shape.exponential(1.0 / arr["rate_per_s"],
                                 int(4 * arr["rate_per_s"] * seconds) + 16)
        due = np.cumsum(gaps)
        due = due[due < seconds]
        n = len(due)
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    p_len = lengths(mix["prompt"], shape, n)
    o_len = lengths(mix["output"], shape, n)
    clients = int(arr.get("clients", 0))
    reqs = [Request(rid=i,
                    prompt=[int(t) for t in tok.integers(1, vocab, p_len[i])],
                    max_tokens=int(o_len[i]), due_s=float(due[i]),
                    client=i % clients if clients else -1)
            for i in range(n)]
    return Traffic(requests=reqs, clients=clients)


def prefill_buckets(mix: dict, page_size: int) -> List[int]:
    """Power-of-two prefill sizes (the engine's buckets, at least a page)
    that the mix's prompts reach.  The engine ingests a prompt but its
    last token (the first decode step feeds that one)."""
    def bucket(n: int) -> int:
        return max(page_size, 1 << (max(n, 1) - 1).bit_length())

    out = []
    b = bucket(mix["prompt"]["min"] - 1)
    while b <= bucket(mix["prompt"]["max"] - 1):
        out.append(b)
        b *= 2
    return out


def closed_order(traffic: Traffic) -> Optional[List[List[Request]]]:
    """Per-client queues of a closed-loop mix, in send order."""
    if not traffic.clients:
        return None
    q: List[List[Request]] = [[] for _ in range(traffic.clients)]
    for r in traffic.requests:
        q[r.client].append(r)
    return q
