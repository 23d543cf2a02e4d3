"""Expert store: share of the expert blocks that dispatches read which
were already in the HBM cache before their layer's fetches of the step
(neither committed from its prefetch nor fetched on demand), over the
window.  Moves ``tok_s``."""


def read(w, suffix):
    needed = w.delta("expert_needed_blocks")
    if "expert_resident_hits" not in w.counters1 or not needed:
        return None
    return 100.0 * w.delta("expert_resident_hits") / needed
