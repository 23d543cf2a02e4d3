"""Engine step: tokens delivered per step in the window (a metric a test
adds as a file)."""


def read(w, suffix):
    steps = w.window_steps()
    return sum(len(s.tokens) for s in steps) / len(steps) if steps else None
