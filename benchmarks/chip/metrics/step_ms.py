"""Engine step: mean host-clock time of one ``LLM.step`` call over the
window, from the benchmark's span around it (the suffix names the cell
kind: ``batch`` moves ``tok_s``, ``chat`` moves ``itl_p95_ms``)."""


def read(w, suffix):
    steps = w.window_steps()
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
