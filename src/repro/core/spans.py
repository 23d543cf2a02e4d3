"""Named host spans on the profiler's own clock.

``with span("repro.engine.step", step=n): ...`` marks a stretch of host
code in the trace that ``jax.profiler`` writes, beside the device's
operations, so an idle gap on the device can be put down to what the host
was doing in it.  The profiler records a span only while a trace is
active; otherwise entering one costs the construction of a small native
object and reads no clock.  Spans mark host code only: inside a jitted
function a span would time the tracing, not the run.

Names start with ``repro.`` and follow the layers: ``repro.engine.*``
(the step and its phases), ``repro.kv.*``, ``repro.dispatch.*`` (the
enqueue of a jitted program), ``repro.sync.*`` (a blocking device-to-host
read), ``repro.expert.*`` and ``repro.guidance.*``;
``repro.engine.state`` marks a recurrent-state slot handed out or given
back (models with Mamba layers).
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A context manager recording ``name`` (with ``args`` as metadata)
    over the ``with`` block while the profiler is tracing."""
    return TraceAnnotation(name, **args)
