"""Expert store: share of the prefetched expert blocks whose staged slab
had already landed in HBM when their layer's dispatch committed them, over
the window: 100 x delta ``expert_landed_blocks`` / delta
``expert_committed_blocks``.  Near 100 the lead hides the transfers; near 0
each dispatch waits for its slab.  Moves ``tok_s``."""


def read(w, suffix):
    committed = w.delta("expert_committed_blocks")
    if "expert_landed_blocks" not in w.counters1 or not committed:
        return None
    return 100.0 * w.delta("expert_landed_blocks") / committed
