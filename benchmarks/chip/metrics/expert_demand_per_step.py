"""Expert store: expert blocks fetched on demand (a routed expert not in
the HBM cache, its prefetch missed) per engine step in the window.  Moves
``tok_s``."""


def read(w, suffix):
    steps = w.delta("steps")
    if "expert_demand_fetches" not in w.counters1 or not steps:
        return None
    return w.delta("expert_demand_fetches") / steps
