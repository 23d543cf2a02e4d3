"""One run of one cell: build it from its files, warm it up, measure for
the run's seconds, check what the window produced against the plain
reference, and print the result line.

Everything that belongs to a cell is found by name:

* ``BENCHMARK.json``: the workload entry (configuration, traffic, chips)
  and the metrics that apply to it;
* ``configs/<file>`` (named by the configuration entry): the model, its
  sizes, the reference it is checked against;
* ``traffic/<mix>.json``: the mix's parameters (``traffic.py``);
* ``cells/<workload>.json``: the serving settings (``ServeConfig``), the
  warm-up, and the correctness check with its limit;
* ``metrics/<name>.py``, else ``metrics/<name before the first dot>.py``:
  the reader of a per-layer metric, ``read(window, suffix)``;
* ``references/<name>.py``: the plain reference.

The program is reached only through ``repro.serve``, ``repro.configs`` and
``repro.models``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

import peaks
import traffic as traffic_mod
import profile_trace as trace_mod

BENCH_DIR = Path(__file__).resolve().parent
# Request ids of the warm-up, apart from the window's (0, 1, ...).
WARM_RID = 10_000_000


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


# ------------------------------------------------------------------ spec
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file's contents
    mix: dict                 # the traffic mix
    settings: dict            # cells/<name>.json
    dir: Path                 # the benchmark's directory
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def load_cell(root: Path, name: str, bench_dir: Path = BENCH_DIR) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    mix = traffic_mod.load(bench_dir / "traffic" / f"{w['traffic']}.json")
    with open(bench_dir / "cells" / f"{name}.json") as f:
        settings = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                settings=settings, dir=bench_dir,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def _load_file(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir: Path, name: str) -> Callable:
    """``read(window, suffix)`` of a per-layer metric, from its own file or
    from the file of its base name (the part before the first dot)."""
    own = bench_dir / "metrics" / f"{name}.py"
    base, _, suffix = name.partition(".")
    path = own if own.exists() else bench_dir / "metrics" / f"{base}.py"
    mod = _load_file(path, f"chip_metric_{base}")
    if own.exists():
        return lambda window: mod.read(window, "")
    return lambda window: mod.read(window, suffix)


def reference_module(bench_dir: Path, config: dict):
    name = config["reference"]
    return _load_file(bench_dir / "references" / f"{name}.py",
                      f"chip_reference_{name}")


# ---------------------------------------------------------------- devices
def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU, only {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX finds {len(devs)}")
    return devs


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program
    cached, so only a checkout's first run compiles."""
    import os

    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ model
def build_model(config: dict):
    """The program's model for a configuration file, checked against the
    sizes the file states."""
    from repro.configs import get
    from repro.models import build_model as build

    import dataclasses as dc

    mcfg = dc.replace(get(config["arch"]), remat=False,
                      **config.get("overrides", {}))
    want = config["model"]
    have = {"n_layers": mcfg.n_layers, "d_model": mcfg.d_model,
            "n_heads": mcfg.n_heads, "kv_heads": mcfg.kv_heads,
            "head_dim": mcfg.resolved_head_dim, "d_ff": mcfg.d_ff,
            "vocab": mcfg.vocab, "n_experts": mcfg.n_experts,
            "top_k": mcfg.top_k, "rope_theta": mcfg.rope_theta}
    diff = {k: (have[k], v) for k, v in want.items()
            if k in have and have[k] != v}
    if diff:
        raise ValueError(f"program config differs from the file: {diff}")
    return build(mcfg)


# --------------------------------------------------------------- window
@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    tokens: List[tuple]       # (rid, attention length) per delivered token
    firsts: List[tuple]       # (rid, prompt length) first token this step
    prefill_tokens: int       # prompt tokens the engine ingested
    saved_tokens: int         # prompt tokens served from the prefix cache


@dataclasses.dataclass
class Sent:
    req: traffic_mod.Request
    due: float                # host clock
    handle: object = None
    times: List[float] = dataclasses.field(default_factory=list)


class Driver:
    """The load generator, in the engine's process: each loop turn submits
    every request that is due, then steps the engine once.  Open loop:
    requests are due at their arrival times.  Closed loop: a client's next
    request is due when its last one finished."""

    def __init__(self, llm, clock):
        self.llm = llm
        self.clock = clock
        self.annotate = _annotator(False)
        self.sent: Dict[int, Sent] = {}
        self.live: Dict[int, Sent] = {}
        self.queues: Optional[List[List[traffic_mod.Request]]] = None
        self.open: List[traffic_mod.Request] = []
        self.steps: List[Step] = []
        self.late_s: List[float] = []

    def schedule(self, traf: traffic_mod.Traffic) -> None:
        """The requests the next ``run`` sends."""
        self.queues = traffic_mod.closed_order(traf)
        self.open = ([] if self.queues is not None
                     else sorted(traf.requests, key=lambda r: r.due_s))

    def submit(self, req, due: float) -> None:
        from repro.serve import SamplingParams

        sp = SamplingParams(max_tokens=req.max_tokens, temperature=0.0)
        s = Sent(req=req, due=due)
        s.handle = self.llm.submit(req.prompt, sp, request_id=req.rid)
        self.sent[req.rid] = s
        self.live[req.rid] = s

    def submit_due(self, t0: float) -> None:
        now = self.clock()
        if self.queues is not None:
            for q in self.queues:
                if q and not (q[0].rid in self.live):
                    if q[0].rid in self.sent:
                        q.pop(0)             # finished: the client's next
                    if q:
                        self.submit(q[0], now)
            return
        while self.open and t0 + self.open[0].due_s <= now:
            r = self.open.pop(0)
            self.late_s.append(now - (t0 + r.due_s))
            self.submit(r, t0 + r.due_s)

    def busy(self) -> bool:
        return bool(self.live)

    def step(self) -> Step:
        eng = self.llm.engine
        p0, s0 = eng.prefill_tokens, eng.saved_prefill_tokens
        t0 = self.clock()
        with self.annotate("bench.step"):
            out = self.llm.step()
        t1 = self.clock()
        toks, firsts = [], []
        for rid in out:
            s = self.sent.get(rid)
            if s is None:
                continue
            s.times.append(t1)
            n = len(s.req.prompt)
            toks.append((rid, n + len(s.times) - 1))
            if len(s.times) == 1:
                firsts.append((rid, n))
        for rid in [r for r, s in self.live.items() if s.handle.finished]:
            del self.live[rid]
        st = Step(t0, t1, toks, firsts, eng.prefill_tokens - p0,
                  eng.saved_prefill_tokens - s0)
        self.steps.append(st)
        return st

    def run(self, t0: float, seconds: float) -> float:
        """Drive the load from ``t0`` for ``seconds``; returns the window's
        end (the end of the step that crossed it)."""
        end = t0 + seconds
        while True:
            now = self.clock()
            if now >= end:
                return now
            with self.annotate("bench.submit"):
                self.submit_due(t0)
            if self.busy():
                self.step()
            elif self.queues is not None:
                return self.clock()      # a closed loop that ran dry
            else:
                nxt = t0 + self.open[0].due_s if self.open else end
                with self.annotate("bench.wait"):
                    time.sleep(max(0.0, min(nxt, end) - self.clock()))

    def drain(self, max_steps: int = 100000) -> None:
        """Step (untimed) until every submitted request has finished."""
        for _ in range(max_steps):
            if not self.busy():
                return
            self.step()
        raise RuntimeError("requests still live after the drain budget")


# ----------------------------------------------------------- end to end
def quantile(values: List[float], q: float) -> float:
    """The ``q`` quantile (0..1) by linear interpolation between order
    statistics (numpy's default)."""
    return float(np.quantile(np.asarray(values, float), q))


def end_to_end(sent: Dict[int, Sent], t0: float, t1: float) -> Dict:
    """Rates over the whole window; tails over every request due in it,
    one with no first token counted at its age at the window's end."""
    tokens = 0
    ttft: List[float] = []
    gaps: List[float] = []
    for s in sent.values():
        inside = [t for t in s.times if t0 <= t <= t1]
        tokens += len(inside)
        if t0 <= s.due <= t1:
            first = s.times[0] if s.times else None
            ttft.append(((first if first is not None and first <= t1
                          else t1) - s.due))
        for a, b in zip(s.times, s.times[1:]):
            if t0 <= a and b <= t1:
                gaps.append(b - a)
    out = {"tok_s": tokens / (t1 - t0), "tokens": tokens,
           "turns": len(ttft), "gaps": len(gaps)}
    if ttft:
        out["ttft_p90_ms"] = 1e3 * quantile(ttft, 0.90)
        out["ttft_p50_ms"] = 1e3 * quantile(ttft, 0.50)
    if gaps:
        out["itl_p95_ms"] = 1e3 * quantile(gaps, 0.95)
        out["itl_p50_ms"] = 1e3 * quantile(gaps, 0.50)
    return out


# --------------------------------------------------------------- window
@dataclasses.dataclass
class Window:
    """What a per-layer reader sees."""
    t0: float
    t1: float
    steps: List[Step]
    counters0: Dict[str, float]
    counters1: Dict[str, float]
    shape: peaks.Shape
    peak: peaks.Peak
    trace: Optional[trace_mod.Summary]
    tokens: int

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def delta(self, key: str) -> float:
        return self.counters1.get(key, 0) - self.counters0.get(key, 0)

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if s.t0 >= self.t0 and s.t1 <= self.t1]

    def prefill_calls(self) -> List[tuple]:
        """(start, n) of each prompt ingest in the window: a step's ingested
        tokens split over the requests whose first token it delivered,
        in proportion to their prompts, each ingest ending at its prompt's
        last token but one (the first decode feeds the last)."""
        calls = []
        for st in self.window_steps():
            if not st.firsts or not st.prefill_tokens:
                continue
            total = sum(n for _, n in st.firsts)
            left = st.prefill_tokens
            for i, (_, n) in enumerate(st.firsts):
                k = (left if i == len(st.firsts) - 1
                     else int(round(st.prefill_tokens * n / total)))
                k = max(0, min(k, n - 1, left))
                left -= k
                if k:
                    calls.append((n - 1 - k, k))
        return calls


# ---------------------------------------------------------------- checks
def check_sample(sent: Dict[int, Sent], k: int, seed: int) -> List[Sent]:
    """``k`` requests that were served tokens, drawn from the seed, the
    one with the longest context among them."""
    served = sorted((s for s in sent.values() if s.handle.token_ids),
                    key=lambda s: s.req.rid)
    if not served:
        return []
    longest = max(served, key=lambda s: len(s.req.prompt)
                  + len(s.handle.token_ids))
    rest = [s for s in served if s is not longest]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 64), 7]))
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gap_numbers(g: np.ndarray) -> Dict[str, float]:
    """What a check can hold served tokens to, from their gaps below the
    reference's best: the widest gap, the mean gap, and the share of
    tokens that are not the reference's first pick (%)."""
    return {"widest_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "not_first_pick_pct": 100.0 * float(np.mean(g > 0))}


def compare(cell: Cell, abstract, seed: int,
            served: List[tuple], quantize: bool = False) -> Dict[str, float]:
    """Served tokens against the reference, over ``served`` (prompt,
    tokens) pairs; with ``quantize`` also the float8 control's readings
    (``control_<number>``): its own first pick in each served token's
    place, at the same positions."""
    import weights as wmod

    if not served:          # nothing was served: nothing can pass
        return {**gap_numbers(np.array([np.inf])), "compared_tokens": 0}
    ref = reference_module(cell.dir, cell.config)
    seqs = [p + t[:-1] for p, t in served]
    firsts = [len(p) - 1 for p, _ in served]
    top = wmod.top_weights(abstract, seed)
    layers = wmod.LayerWeights(abstract)
    get = lambda l: layers.draw(seed, l)  # noqa: E731
    model = cell.config["model"]
    want = ref.logits(model, get, top, seqs, firsts)
    per = [ref.gaps(r, np.asarray(t)) for r, (_, t) in zip(want, served)]
    g = np.concatenate(per)
    worst = sorted(((float(x), i, j) for i, a in enumerate(per)
                    for j, x in enumerate(a)), reverse=True)[:5]
    print("widest gaps (gap, request, served index): "
          + ", ".join(f"({x:.4f}, {i}, {j})" for x, i, j in worst)
          + f"; lengths {[len(p) for p, _ in served]}", file=sys.stderr)
    if not all(np.isfinite(r).all() for r in want):
        g = np.full_like(g, np.inf)
    out = gap_numbers(g)
    out["compared_tokens"] = int(len(g))
    if quantize:
        low = ref.logits(model, get, top, seqs, firsts, quantize=True)
        gc_ = np.concatenate([ref.gaps(r, q.argmax(-1))
                              for r, q in zip(want, low)])
        out.update({f"control_{k}": v for k, v in gap_numbers(gc_).items()})
    return out


def judge(limits: Dict[str, float], got: Dict[str, float]) -> tuple:
    """Each number the check gives a limit for, beside its limit, and
    whether every one holds: ``compared_tokens`` is held to a floor, the
    others to a ceiling."""
    checks = {k: {"value": got[k], "limit": v} for k, v in limits.items()}
    return checks, all(
        c["value"] >= c["limit"] if k == "compared_tokens"
        else c["value"] <= c["limit"] for k, c in checks.items())


# ------------------------------------------------------------------- run
def _annotator(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return lambda name: jax.profiler.TraceAnnotation(name)


def _counters(llm) -> Dict[str, float]:
    return {k: v for k, v in llm.stats().items()
            if isinstance(v, (int, float))}


def warm_prompts(cell: Cell, vocab: int, seed: int) -> List[List[int]]:
    """One prompt per prefill bucket the window can reach."""
    P = cell.settings["serve"]["page_size"]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) % (1 << 64), 3]))
    return [[int(t) for t in rng.integers(1, vocab, b)]
            for b in traffic_mod.prefill_buckets(cell.mix, P)]


@dataclasses.dataclass
class Server:
    """A cell's server, built from its files and the seed."""
    cell: Cell
    devs: list
    peak: peaks.Peak
    compiles: object
    abstract: object
    params: object
    llm: object
    shape: peaks.Shape
    vocab: int


def build(root: Path, name: str, seed: int, clock=time.perf_counter,
          setup_hook=None, bench_dir: Path = BENCH_DIR) -> Server:
    """The cell's server with weights from ``seed``, on the chip.
    ``setup_hook(llm)`` runs once it is built (tests plant faults there)."""
    cell = load_cell(root, name, bench_dir)
    enable_compile_cache(root)
    devs = require_chips(cell.chips)
    peak = peaks.peak_for(devs[0].device_kind)
    import weights as wmod

    compiles = wmod.CompileClock(clock)
    from repro.serve import LLM, ServeConfig

    model = build_model(cell.config)
    abstract = model.abstract()
    params = wmod.init_weights(abstract, seed)
    serve = cell.settings["serve"]
    llm = LLM(model, params, ServeConfig(**serve))
    for path in cell.settings.get("drop_after_build", []):
        node = params
        for k in path.split("/"):
            node = node[k]
        node.delete()
    if setup_hook is not None:
        setup_hook(llm)
    return Server(cell, devs, peak, compiles, abstract, params, llm,
                  peaks.Shape.from_config(cell.config["model"]),
                  cell.config["model"]["vocab"])


def prepare(srv: Server, traf: traffic_mod.Traffic, seed: int,
            clock=time.perf_counter) -> "Driver":
    """Set-up before a window: warm every prefill bucket and the decode
    batch, then fill a closed loop's batch.  Returns the driver, scheduled
    with ``traf``."""
    drv = Driver(srv.llm, clock)
    for i, p in enumerate(warm_prompts(srv.cell, srv.vocab, seed)):
        drv.submit(traffic_mod.Request(rid=WARM_RID + i, prompt=p,
                                       max_tokens=2), clock())
    drv.drain()
    drv.schedule(traf)
    if drv.queues is not None:
        drv.submit_due(clock())
        drv.step()          # admits and prefills the whole batch
    return drv


def run(root: Path, args, t_start: float, clock=time.perf_counter,
        setup_hook=None, bench_dir: Path = BENCH_DIR) -> Dict:
    """One run; returns the result dict (the last line's contents)."""
    import jax

    srv = build(root, args.workload, args.seed, clock, setup_hook, bench_dir)
    cell, llm, compiles, params = srv.cell, srv.llm, srv.compiles, srv.params
    devs, dev, peak, shape = srv.devs, srv.devs[0], srv.peak, srv.shape
    abstract = srv.abstract
    traf = traffic_mod.generate(cell.mix, args.seed, srv.vocab, args.seconds)
    drv = prepare(srv, traf, args.seed, clock)
    drv.annotate = _annotator(bool(args.trace))
    del srv
    import weights as wmod

    # ---- the window
    trace_dir = None
    if args.trace:
        trace_dir = Path(tempfile.mkdtemp(prefix="chipbench_trace_"))
        trace_mod.start(str(trace_dir))
    counters0 = _counters(llm)
    t0 = clock()
    setup_s = t0 - t_start
    with drv.annotate("bench.window"):
        t1 = drv.run(t0, args.seconds)
    counters1 = _counters(llm)
    if args.trace:
        jax.profiler.stop_trace()
    in_window = compiles.count_between(t0, t1)
    mem_peak = wmod.peak_bytes(dev)
    e2e = end_to_end(drv.sent, t0, t1)
    print(json.dumps({"window_compiles": in_window, "setup_s": setup_s,
                      "setup_backend_compile_s": compiles.backend_seconds(),
                      "window_s": t1 - t0, "steps": len(
                          [s for s in drv.steps if t0 <= s.t0 < t1]),
                      "generator_late_p50_s": (statistics.median(
                          drv.late_s) if drv.late_s else 0.0),
                      **{k: v for k, v in e2e.items()}}), flush=True)

    summary = None
    if trace_dir is not None:
        summary = trace_mod.summarize(trace_mod.load_dir(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- correctness, with the program's state freed
    chk = cell.settings["check"]
    mine = {rid: s for rid, s in drv.sent.items() if rid < WARM_RID}
    served = [(list(s.req.prompt), [int(t) for t in s.handle.token_ids])
              for s in check_sample(mine, int(chk["requests"]), args.seed)]
    attempted = sum(1 for s in mine.values() if s.due <= t1)
    failed = sum(1 for s in mine.values()
                 if s.handle.finish_reason in ("truncated", "cancelled"))
    window = Window(t0, t1, drv.steps, counters0, counters1, shape, peak,
                    summary, e2e["tokens"])
    # Handles hold the LLM: drop every one before the reference runs.
    del llm, params, drv, mine
    gc.collect()
    control = getattr(args, "control", False)
    got = compare(cell, abstract, args.seed, served, quantize=control)
    print("readings: " + json.dumps(got), file=sys.stderr)
    checks, correct = judge(chk["limits"], got)
    if control:
        # The control in the program's place, held to the same limits.
        program = {"checks": checks, "correct": correct}
        checks, correct = judge(chk["limits"], {
            **got, **{k[len("control_"):]: v for k, v in got.items()
                      if k.startswith("control_")}})

    metrics = {}
    if args.trace:
        for m in cell.per_layer:
            v = metric_reader(cell.dir, m["name"])(window)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    if control:
        result["program"] = program
    result["checks"] = checks
    return result


def print_result(result: Dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
