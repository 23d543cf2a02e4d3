"""Kernel ``kernels/paged_attention.py``: its device time in the trace
(operations named ``paged_attention_pallas`` and ``paged_prefill_pallas``)
against the least time the chip needs for the work it did: every decode
row over its context and every prompt ingest, each layer
(``peaks.paged_attention_cost``), in %."""

import peaks

KERNELS = ("paged_attention_pallas", "paged_prefill_pallas")


def read(w, suffix):
    if w.trace is None:
        return None
    seconds, calls = w.trace.kernel_seconds(KERNELS)
    if not calls or seconds <= 0:
        return None
    rows = [(n - 1, 1) for s in w.window_steps() for _, n in s.tokens]
    rows += w.prefill_calls()
    flops, nbytes = peaks.paged_attention_cost(w.shape, rows)
    L = w.shape.n_layers
    share, _ = peaks.roofline_share(L * flops, L * nbytes, seconds, w.peak)
    return share
