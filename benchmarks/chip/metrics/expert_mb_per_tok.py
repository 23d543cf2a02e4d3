"""Expert store: MB of expert weights copied from pinned host memory to
the HBM cache (demand and prefetch) per generated token in the window.
Moves ``tok_s``."""


def read(w, suffix):
    if "expert_bytes_fetched" not in w.counters1 or not w.tokens:
        return None
    return w.delta("expert_bytes_fetched") / 1e6 / w.tokens
