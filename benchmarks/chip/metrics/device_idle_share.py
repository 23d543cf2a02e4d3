"""Device: share of the traced window in which no operation ran on the
chip, in % (1 - union of the device's operation intervals over the
window)."""


def read(w, suffix):
    if w.trace is None or not w.trace.window_s:
        return None
    return 100.0 * w.trace.idle_share
