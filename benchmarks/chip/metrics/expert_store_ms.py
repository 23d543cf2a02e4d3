"""Expert store: milliseconds per window step of host time in
``ExpertStore``: the self time of the program's ``repro.expert.*`` spans
(dispatch and prefetch bookkeeping, host-to-HBM transfers and cache row
writes) in the trace, over the window's steps.  Moves ``tok_s``.  Needs a
trace reduced with the program's spans (``span_trace``); None
otherwise."""

import span_trace

SPANS = ("repro.expert.",)


def read(w, suffix):
    return span_trace.ms_per_step(w, SPANS)
