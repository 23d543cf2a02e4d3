"""Engine step: milliseconds per window step in which the host waits in a
blocking device-to-host read: the self time of the program's
``repro.sync.*`` spans (each layer's routed picks, the speculative
router's forecasts, the sampled tokens) in the trace, over the window's
steps.  Moves ``tok_s``.  Needs a trace reduced with the program's spans
(``span_trace``); None otherwise."""

import span_trace

SPANS = ("repro.sync.",)


def read(w, suffix):
    return span_trace.ms_per_step(w, SPANS)
