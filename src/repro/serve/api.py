"""The front-door generation API: ``LLM.generate`` / ``LLM.submit``.

This is the surface behind which guided KV tiering stays invisible — the
paper's "no source code modification" claim applied to serving: callers
express *what* to generate (prompts + ``SamplingParams``), and the engine's
continuous batching, paged two-tier KV cache, preemption-by-recompute and
Algorithm-1 page placement all happen behind it.

Two entry points over one shared engine:

* ``generate(prompts, params)`` — batch-blocking: submit everything, step
  the engine until every request finishes, return ``RequestOutput`` rows in
  prompt order.
* ``submit(prompt, params) -> RequestHandle`` — streaming: the handle is an
  iterable of ``(token, finish_reason)`` deltas produced as the engine
  steps; ``finish_reason`` is ``None`` until the final delta (``stop`` /
  ``length`` / ``truncated`` / ``cancelled``).  Iterating a handle drives
  the shared engine, so concurrent handles make progress together.
  ``LLM.cancel(request_id)`` withdraws a live request; its handle ends
  with a ``cancelled`` delta instead of dangling.

Determinism: with ``temperature=0`` the output is bitwise-equal to greedy
decode; with a seeded ``temperature > 0`` the stream is a pure function of
(request stream, seed, position), so engine-internal preemption and
recompute never change what a caller observes (DESIGN.md §10).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..core import TPU_V5E, HardwareModel
from .cluster import ReplicaLostError, Router
from .engine import Engine, ServeConfig
from .sampling import DEFAULT_MAX_TOKENS, SamplingParams

Prompt = Sequence[int]
Delta = Tuple[Optional[int], Optional[str]]


@dataclasses.dataclass
class RequestOutput:
    """One finished request, as the front door reports it."""

    request_id: int
    prompt_token_ids: List[int]
    token_ids: List[int]
    finish_reason: str            # stop | length | truncated | cancelled
    params: SamplingParams


class RequestHandle:
    """Streaming view of one submitted request.

    Iterate to receive ``(token, finish_reason)`` deltas: every generated
    token arrives as ``(token, None)`` except the last, which carries the
    finish reason; a request finished without a token this step (capacity
    truncation) emits a final tokenless ``(None, reason)`` delta.
    Iteration drives the shared engine, so other in-flight handles advance
    too.  ``result()`` drains to completion and returns the
    ``RequestOutput``.
    """

    def __init__(self, llm: "LLM", request_id: int, prompt: Prompt,
                 params: SamplingParams):
        self._llm = llm
        self.request_id = request_id
        self.prompt_token_ids = [int(t) for t in prompt]
        self.params = params
        self.token_ids: List[int] = []
        self.finish_reason: Optional[str] = None
        self._deltas: Deque[Delta] = deque()
        self._queued = 0        # prefix of req.generated queued as deltas

    @property
    def finished(self) -> bool:
        return self.finish_reason is not None

    def __iter__(self) -> Iterator[Delta]:
        while True:
            delta = self.next_delta()
            if delta is None:
                return
            yield delta

    def next_delta(self) -> Optional[Delta]:
        """Block (stepping the engine) until this request's next delta, or
        ``None`` when the stream is exhausted."""
        while not self._deltas:
            if self.finished:
                return None
            # The engine may have been stepped directly (bypassing
            # llm.step): absorb any finish before deciding to step again.
            self._llm._absorb_finished()
            if self._deltas or self.finished:
                continue
            req = self._llm.engine.requests.get(self.request_id)
            if req is None:
                # The owning replica may have left the cluster without a
                # survivor to rebuild the request: a NAMED error, never a
                # hang or a raw KeyError (cold migration is transparent —
                # a recovered request is simply live again on its new
                # replica by the time we look).
                lost = getattr(self._llm.engine, "lost_requests", None)
                if lost and self.request_id in lost:
                    raise ReplicaLostError(
                        f"request {self.request_id} was lost: "
                        f"{lost[self.request_id]}; resubmit to retry")
                # Not live and not absorbable from engine.finished (the
                # absorb above would have caught that): the result was
                # drained behind our back — fail loudly rather than
                # busy-stepping an engine that no longer has the request.
                raise RuntimeError(
                    f"request {self.request_id} left the engine without "
                    f"its result reaching this handle (was "
                    f"engine.pop_finished called directly?)")
            if req.state in ("paused", "preempted"):
                # Single-threaded driver: stepping can never advance a
                # request the caller parked, so spinning would hang.
                raise RuntimeError(
                    f"request {self.request_id} is {req.state}; resume() "
                    f"it before consuming its stream")
            self._llm.step()
        return self._deltas.popleft()

    def result(self) -> RequestOutput:
        for _ in self:
            pass
        return RequestOutput(
            request_id=self.request_id,
            prompt_token_ids=list(self.prompt_token_ids),
            token_ids=list(self.token_ids),
            finish_reason=self.finish_reason,
            params=self.params)


class LLM:
    """Generation front end over a cluster of serving engine replicas.

    Construct from a built model (``LLM(model, params)``) or straight from
    the architecture registry (``LLM.from_arch("llama3_2_1b")``).  All
    tiering/scheduling knobs stay on ``ServeConfig``; per-request behaviour
    stays on ``SamplingParams`` — the caller never touches pages, tiers or
    batches.  ``replicas=N`` puts N engines behind the same front door
    (``serve.cluster.Router``), replica *i* on ``jax.devices()[i % n]``
    with its own copy of ``params``: requests dispatch least-loaded, and
    replica failure/drain migrates in-flight streams bitwise (across
    chips) instead of dropping them.  The default ``replicas=1`` behaves
    exactly like the old single-engine LLM — ``llm.engine`` then delegates
    engine attributes transparently.
    """

    def __init__(self, model, params, cfg: Optional[ServeConfig] = None,
                 hw: HardwareModel = TPU_V5E, replicas: int = 1,
                 heartbeat_timeout: float = 8.0):
        cfg = cfg or ServeConfig()
        devices = jax.devices()
        self.cluster = Router(
            lambda rid: Engine(model, params, cfg, hw,
                               device=devices[rid % len(devices)]),
            n_replicas=replicas, heartbeat_timeout=heartbeat_timeout)
        self._handles: Dict[int, RequestHandle] = {}
        self._next_id = 0

    @property
    def engine(self) -> Router:
        """The engine-shaped control surface (the ``Router``): merged
        ``requests``/``finished`` views, cluster ``stats()``, and — on a
        one-replica cluster — transparent delegation of single-engine
        attributes (``engine.pool``, ``engine.prefix_cache``, ...)."""
        return self.cluster

    @classmethod
    def from_arch(cls, arch: str, smoke: bool = True,
                  cfg: Optional[ServeConfig] = None,
                  seed: int = 0, replicas: int = 1) -> "LLM":
        from ..configs import get, get_smoke
        from ..models import build_model

        mcfg = get_smoke(arch) if smoke else get(arch)
        mcfg = dataclasses.replace(mcfg, remat=False)
        model = build_model(mcfg)
        return cls(model, model.init(jax.random.PRNGKey(seed)), cfg,
                   replicas=replicas)

    # ------------------------------------------------------------ submit
    def submit(self, prompt: Prompt,
               params: Optional[SamplingParams] = None,
               request_id: Optional[int] = None,
               replica_id: Optional[int] = None) -> RequestHandle:
        """Enqueue one request and return its streaming handle.  Dispatch
        is least-loaded across alive replicas; ``replica_id`` pins it."""
        params = params if params is not None else SamplingParams()
        rid = request_id if request_id is not None else self._next_id
        self._next_id = max(self._next_id, rid + 1)
        # The generation budget resolves inside add_request (max_tokens,
        # else DEFAULT_MAX_TOKENS) — one owner, no api-side duplicate.
        self.cluster.add_request(rid, [int(t) for t in prompt],
                                 params=params, replica_id=replica_id)
        handle = RequestHandle(self, rid, prompt, params)
        self._handles[rid] = handle
        return handle

    def generate(self,
                 prompts: Union[Prompt, Sequence[Prompt]],
                 params: Union[None, SamplingParams,
                               Sequence[SamplingParams]] = None,
                 ) -> List[RequestOutput]:
        """Batch-blocking generation: one output row per prompt, in order.

        ``prompts`` is a list of token-id lists (a single flat token list
        is treated as one prompt); ``params`` is shared or per-prompt.
        """
        if prompts and isinstance(prompts[0], (int, np.integer)):
            prompts = [prompts]
        if params is None or isinstance(params, SamplingParams):
            plist: List[Optional[SamplingParams]] = [params] * len(prompts)
        else:
            if len(params) != len(prompts):
                raise ValueError(
                    f"{len(params)} SamplingParams for {len(prompts)} "
                    f"prompts")
            plist = list(params)
        handles = [self.submit(p, sp) for p, sp in zip(prompts, plist)]
        while any(not h.finished for h in handles):
            self.step()
        return [h.result() for h in handles]

    # ----------------------------------------------------------- driving
    def step(self) -> Dict[int, int]:
        """Advance the engine one step and route deltas to their handles."""
        out = self.engine.step()
        for rid in out:
            h = self._handles.get(rid)
            req = (self.engine.requests.get(rid)
                   or self.engine.finished.get(rid))
            if h is not None and req is not None:
                self._route(h, req.generated)
        self._absorb_finished()
        return out

    @staticmethod
    def _route(h: RequestHandle, generated: Sequence[int]) -> None:
        """Queue every not-yet-queued token of the request's authoritative
        stream as a ``(token, None)`` delta.  Routing always reconciles
        against ``req.generated`` with a per-handle cursor, so tokens
        produced while the engine was stepped directly (bypassing
        ``llm.step``) are delivered in order, never duplicated."""
        for tok in generated[h._queued:]:
            h.token_ids.append(int(tok))
            h._deltas.append((int(tok), None))
        h._queued = len(generated)

    def _absorb_finished(self) -> None:
        """Move engine finishes onto their handles: the final undelivered
        token delta gains the ``finish_reason``; a request that finished
        without producing a token this step (capacity truncation) gets a
        trailing tokenless ``(None, reason)`` delta."""
        for rid in list(self.engine.finished):
            # Finished handles leave the routing table: the handle object
            # itself (with its tokens) belongs to the caller, and keeping a
            # reference per past request would grow without bound in a
            # long-lived server (the API-layer twin of the engine's
            # finished-request leak fix).
            h = self._handles.pop(rid, None)
            if h is None or h.finished:
                continue                 # engine driven directly / drained
            req = self.engine.pop_finished(rid)
            h.finish_reason = req.finish_reason
            self._route(h, req.generated)
            if h._deltas and h._deltas[-1][1] is None:
                tok, _ = h._deltas.pop()
                h._deltas.append((tok, req.finish_reason))
            else:
                h._deltas.append((None, req.finish_reason))

    # -------------------------------------------------- session controls
    def pause(self, request_id: int) -> None:
        self.engine.pause(request_id)

    def resume(self, request_id: int) -> None:
        self.engine.resume(request_id)

    def cancel(self, request_id: int) -> None:
        """Withdraw a live request.  Its streaming handle terminates with
        a final ``(token-or-None, "cancelled")`` delta (tokens generated
        before the cancel are still delivered); ``result()`` returns them
        with ``finish_reason="cancelled"``.  Finished or unknown ids raise
        the engine's named ``ValueError``."""
        self.engine.cancel(request_id)
        self._absorb_finished()

    def is_live(self, request_id: int) -> bool:
        """True while the request is still inside the engine (any state
        short of finished) — the guard session drivers use before
        pause/resume, which raise on finished/unknown ids."""
        return request_id in self.engine.requests

    def stats(self) -> Dict[str, float]:
        return self.engine.stats()
