"""Engine step: blocking device-to-host reads per engine step in the
window (``Engine.stats()["host_syncs"]``: each layer's routed picks, the
speculative router's forecasts, the sampled tokens).  Each read waits for
the device work before it.  Moves ``tok_s``."""


def read(w, suffix):
    steps = w.delta("steps")
    if "host_syncs" not in w.counters1 or not steps:
        return None
    return w.delta("host_syncs") / steps
