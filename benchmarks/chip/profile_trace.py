"""From a profiler trace to the device's busy time, its operations and
its idle gaps, with each gap put down to what the host was doing in it.

A trace is reduced to plain events ``(plane, line, name, start_ns,
dur_ns)``.  Device operations are the events of the ``XLA Ops`` line of
each ``/device:`` plane; host spans are the benchmark's own annotations
(names starting ``bench.``), of which ``bench.window`` bounds the window.
Busy time is the union of the operations' intervals inside the window,
averaged over the devices that ran any.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def start(trace_dir: str) -> None:
    """Start the profiler without its Python-function tracer (which would
    trace every call the host makes, and slow it)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def load_dir(trace_dir: Path) -> List[Event]:
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    events: List[Event] = []
    for path in files:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            dev = plane.name.startswith("/device:")
            for line in plane.lines:
                if dev and line.name != OPS_LINE:
                    continue
                for e in line.events:
                    if not dev and not e.name.startswith(SPAN_PREFIX):
                        continue
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns)))
    return events


def op_name(name: str) -> str:
    """An operation's instruction name without XLA's ``.N`` instance
    suffix.  The TPU trace names an operation by its HLO text,
    ``%paged_attention_pallas.1 = bf16[...] custom-call(...)``; the name
    is what stands before ``=``."""
    m = re.match(r"\s*%?([^\s=]+)\s*=", name)
    return re.sub(r"\.\d+$", "", m.group(1) if m else name)


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]          # device seconds per operation name
    op_calls: Dict[str, int]
    gaps_by_span: Dict[str, float]  # idle seconds by what the host did

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, prefixes: Sequence[str]) -> Tuple[float, int]:
        """Device seconds and call count of the operations whose name
        starts with one of ``prefixes``."""
        s, n = 0.0, 0
        for name, t in self.op_s.items():
            if any(name.startswith(p) for p in prefixes):
                s += t
                n += self.op_calls[name]
        return s, n

    def breakdown(self) -> Dict[str, list]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def summarize(events: List[Event]) -> Summary:
    win = [e for e in events if e.name == WINDOW]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win[0].start_ns, win[0].end_ns
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
             and e.name != WINDOW]
    by_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_s: Dict[str, float] = defaultdict(float)
    op_calls: Dict[str, int] = defaultdict(int)
    for e in events:
        if not e.plane.startswith("/device:"):
            continue
        iv = clip([(e.start_ns, e.end_ns)], lo, hi)
        if not iv:
            continue
        by_dev[e.plane].extend(iv)
        name = op_name(e.name)
        op_s[name] += (iv[0][1] - iv[0][0]) * 1e-9
        op_calls[name] += 1
    busy = [merge(v) for v in by_dev.values()]
    busy_ns = (sum(sum(b - a for a, b in m) for m in busy) / len(busy)
               if busy else 0.0)
    # Idle gaps of the first device, each put down to the host span that
    # covers most of it ("host" where none does).  The spans run one after
    # another, so walking back from the last one that starts before a gap
    # ends reaches every span that overlaps it.
    gaps: List[Tuple[float, float]] = []
    if busy:
        cur = lo
        for a, b in busy[0]:
            if a > cur:
                gaps.append((cur, a))
            cur = max(cur, b)
        if cur < hi:
            gaps.append((cur, hi))
    spans.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    by_span: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        best, cover = "host", 0.0
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and spans[i].end_ns > a:
            s = spans[i]
            c = min(b, s.end_ns) - max(a, s.start_ns)
            if c > cover:
                best, cover = s.name, c
            i -= 1
        by_span[best] += (b - a) * 1e-9
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                   op_s=dict(op_s), op_calls=dict(op_calls),
                   gaps_by_span=dict(by_span))
