"""Model step: model operations of every token the window processed
(each decoded token, and each prompt token ingested), over the window's
seconds times the chip's peak, in %.  Operations per token: two per
multiplied weight (attention projections, the routed experts or MLP,
the head), plus attention over the token's context
(``peaks.model_flops``)."""

import peaks


def read(w, suffix):
    steps = w.window_steps()
    if not steps:
        return None
    flops = 0
    for s in steps:
        flops += peaks.model_flops(w.shape, [n for _, n in s.tokens])
    per = 2 * w.shape.matmul_params_per_token()
    for start, n in w.prefill_calls():
        keys = n * start + n * (n + 1) // 2
        flops += n * per + peaks.attention_flops(w.shape, 1) * keys
    return 100.0 * flops / (w.seconds * w.peak.flops_bf16)
