"""Kernel ``kernels/moe_gemm.py``: the grouped expert SwiGLU's device time
in the trace (operations named ``moe_grouped_ffn_pallas``) against the
least time the chip needs for it: the routed (token, expert) rows of every
dispatch in the window, each layer, and the weights of the experts they
reach read once (``peaks.moe_gemm_cost``; distinct experts per dispatch
counted as routing spread evenly would reach them), in %."""

import peaks

KERNELS = ("moe_grouped_ffn_pallas",)


def read(w, suffix):
    if w.trace is None or not w.shape.moe:
        return None
    seconds, calls = w.trace.kernel_seconds(KERNELS)
    if not calls or seconds <= 0:
        return None
    E, k = w.shape.n_experts, w.shape.top_k
    dispatches = [len(s.tokens) for s in w.window_steps() if s.tokens]
    dispatches += [n for _, n in w.prefill_calls()]
    flops = nbytes = 0
    for rows in dispatches:
        f, b = peaks.moe_gemm_cost(
            w.shape, rows * k, peaks.expected_distinct(E, k, rows))
        flops += f
        nbytes += b
    L = w.shape.n_layers
    share, _ = peaks.roofline_share(L * flops, L * nbytes, seconds, w.peak)
    return share
