"""Guidance runtime: milliseconds per window step of host time in
``GuidanceRuntime.on_step`` (``core/runtime.py``), every controller's: the
self time of the program's ``repro.guidance.*`` spans in the trace, over
the window's steps.  Enforcement moves run inside it and count here unless
a span of their own takes them.  Moves ``tok_s``.  Needs a trace reduced
with the program's spans (``span_trace``); None otherwise."""

import span_trace

SPANS = ("repro.guidance.",)


def read(w, suffix):
    return span_trace.ms_per_step(w, SPANS)
