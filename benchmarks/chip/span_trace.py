"""``profile_trace`` with the program's own spans: the same reduction, and
besides the self time of each span the program writes (``repro.*``,
``src/repro/core/spans.py``) and the idle gaps split down to them.

The program's spans nest on the engine's one thread (``repro.engine.step``
holds ``repro.engine.decode``, which holds a ``repro.sync.route`` per
layer, ...).  They are flattened into a timeline on which each instant
belongs to the innermost span open at it, clipped to ``bench.window``; a
span's *self time* is its length on that timeline.  Each idle gap of the
device is first put down to a ``bench.*`` span as ``profile_trace`` does;
then the parts of the gap that a program span covers go to the innermost
one, and the rest stays with the ``bench.*`` name.

It has ``profile_trace``'s interface (``start``, ``load_dir``,
``summarize``), so a harness can take it in that module's place.  With no
``repro.*`` event in a trace, every field of the summary and its breakdown
are ``profile_trace``'s.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

import profile_trace as pt
from profile_trace import Event, start  # noqa: F401  (the same interface)

PROGRAM_PREFIX = "repro."
HOST_PREFIXES = (pt.SPAN_PREFIX, PROGRAM_PREFIX)

Segment = Tuple[float, float, str]      # (start_ns, end_ns, span name)


def load_dir(trace_dir: Path) -> List[Event]:
    """``profile_trace.load_dir``, keeping the program's host spans too."""
    from jax.profiler import ProfileData

    files = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    events: List[Event] = []
    for path in files:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            dev = plane.name.startswith("/device:")
            for line in plane.lines:
                if dev and line.name != pt.OPS_LINE:
                    continue
                for e in line.events:
                    if not dev and not e.name.startswith(HOST_PREFIXES):
                        continue
                    events.append(Event(plane.name, line.name, e.name,
                                        float(e.start_ns),
                                        float(e.duration_ns)))
    return events


@dataclasses.dataclass
class Summary(pt.Summary):
    span_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    span_calls: Dict[str, int] = dataclasses.field(default_factory=dict)

    def span_seconds(self, prefixes: Sequence[str]) -> Tuple[float, int]:
        """Self seconds and call count of the program spans whose name
        starts with one of ``prefixes``."""
        s, n = 0.0, 0
        for name, t in self.span_s.items():
            if any(name.startswith(p) for p in prefixes):
                s += t
                n += self.span_calls[name]
        return s, n

    def breakdown(self) -> Dict[str, list]:
        out = super().breakdown()
        if self.span_s:
            top = sorted(self.span_s.items(), key=lambda kv: -kv[1])[:10]
            out["program_spans"] = [[k, v] for k, v in top]
        return out


def _innermost(spans: Sequence[Event], lo: float, hi: float
              ) -> List[Segment]:
    """The stretches of ``[lo, hi]`` that nested ``spans`` cover, in
    order, each named by the innermost span open over it.  A child that
    outlasts its parent (clock rounding) is cut at the parent's end."""
    segs: List[Segment] = []
    stack: List[Tuple[float, str]] = []          # (end, name), outermost first
    cur = lo

    def advance(to: float) -> None:
        nonlocal cur
        a, b = max(cur, lo), min(to, hi)
        if stack and b > a:
            segs.append((a, b, stack[-1][1]))
        cur = max(cur, to)

    for e in sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            advance(stack[-1][0])
            stack.pop()
        advance(e.start_ns)
        end = min(e.end_ns, stack[-1][0]) if stack else e.end_ns
        stack.append((end, e.name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return segs


def _first_device_gaps(events: List[Event], lo: float, hi: float
                       ) -> List[Tuple[float, float]]:
    """Idle gaps of the first device, as ``profile_trace.summarize`` takes
    them."""
    first, ivs = None, []
    for e in events:
        if not e.plane.startswith("/device:"):
            continue
        iv = pt.clip([(e.start_ns, e.end_ns)], lo, hi)
        if not iv:
            continue
        first = first or e.plane
        if e.plane == first:
            ivs.extend(iv)
    gaps: List[Tuple[float, float]] = []
    if first is None:
        return gaps
    cur = lo
    for a, b in pt.merge(ivs):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def summarize(events: List[Event]) -> Summary:
    base = pt.summarize(events)          # refuses a trace with no window
    win = next(e for e in events if e.name == pt.WINDOW)
    lo, hi = win.start_ns, win.end_ns
    bench = sorted((e for e in events if e.name.startswith(pt.SPAN_PREFIX)
                    and e.name != pt.WINDOW), key=lambda e: e.start_ns)
    program = [e for e in events if e.name.startswith(PROGRAM_PREFIX)]
    segs = _innermost(program, lo, hi)

    span_s: Dict[str, float] = {}
    for a, b, name in segs:
        span_s[name] = span_s.get(name, 0.0) + (b - a) * 1e-9
    span_calls: Dict[str, int] = {}
    for e in program:
        if e.end_ns > lo and e.start_ns < hi:
            span_calls[e.name] = span_calls.get(e.name, 0) + 1
    for name in span_calls:
        span_s.setdefault(name, 0.0)

    # Each gap to the bench span covering most of it ("host" where none
    # does).  Walking back from the last span that starts before the gap
    # ends, while any earlier span still reaches past the gap's start,
    # meets every span that overlaps it, nested or not.
    starts = [e.start_ns for e in bench]
    reach: List[float] = []
    for e in bench:
        reach.append(max(reach[-1], e.end_ns) if reach else e.end_ns)
    by_span: Dict[str, float] = {}
    j = 0
    for a, b in _first_device_gaps(events, lo, hi):
        best, cover = "host", 0.0
        i = bisect.bisect_left(starts, b) - 1
        while i >= 0 and reach[i] > a:
            s = bench[i]
            c = min(b, s.end_ns) - max(a, s.start_ns)
            if c > cover:
                best, cover = s.name, c
            i -= 1
        # Then the parts a program span covers, to the innermost one.
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        covered = 0.0
        k = j
        while k < len(segs) and segs[k][0] < b:
            c = min(b, segs[k][1]) - max(a, segs[k][0])
            if c > 0:
                by_span[segs[k][2]] = by_span.get(segs[k][2], 0.0) + c * 1e-9
                covered += c
            k += 1
        if not covered:
            by_span[best] = by_span.get(best, 0.0) + (b - a) * 1e-9
        elif b - a - covered > 1e-3:
            by_span[best] = (by_span.get(best, 0.0)
                             + (b - a - covered) * 1e-9)
    return Summary(window_s=base.window_s, busy_s=base.busy_s,
                   op_s=base.op_s, op_calls=base.op_calls,
                   gaps_by_span=by_span, span_s=span_s,
                   span_calls=span_calls)


def ms_per_step(w, prefixes: Sequence[str]):
    """Self time of the program spans named by ``prefixes``, in ms per
    window step of a per-layer reader's window ``w``; None where the trace
    was not reduced with the program's spans or holds none of these."""
    seconds = getattr(w.trace, "span_seconds", None)
    steps = w.window_steps()
    if seconds is None or not steps:
        return None
    s, n = seconds(prefixes)
    if not n:
        return None
    return 1e3 * s / len(steps)
