"""Kernel ``paged_mla_decode_pallas`` (``kernels/paged_attention.py``,
absorbed multi-head latent attention over paged latents): its device time
in the trace against the least time the chip needs for the window's decode
rows, every layer, in %.

Per decode row and layer, over a context of c tokens (``cost``): scores
and weighted latents, 2 * H * c * (L + C) operations, where L is the
cached latent's width (kv_lora_rank + qk_rope_head_dim) and C the value
lanes (kv_lora_rank); bytes, the context's latents read once (c * L) and
the absorbed query (H * L) and output (H * C) once.  ``peaks.Shape`` has
no field for the latent widths, so they come from the configuration file
of the one cell that runs the kernel (``CONFIG``).  A trace without the
kernel (a program without latent attention) reads nothing."""

import json
from pathlib import Path
from typing import Iterable, Tuple

import peaks

KERNELS = ("paged_mla_decode_pallas",)
CONFIG = Path(__file__).resolve().parents[1] / "configs" / (
    "deepseek-v2-lite.7of27.json")


def cost(heads: int, latent: int, values: int, contexts: Iterable[int],
         dtype_bytes: int = 2) -> Tuple[int, int]:
    """(operations, bytes) of one layer's absorbed decode over the decode
    rows whose contexts are ``contexts``."""
    flops = nbytes = 0
    for c in contexts:
        flops += 2 * heads * c * (latent + values)
        nbytes += (c * latent + heads * latent + heads * values) * dtype_bytes
    return flops, nbytes


def read(w, suffix):
    if w.trace is None:
        return None
    seconds, calls = w.trace.kernel_seconds(KERNELS)
    if not calls or seconds <= 0:
        return None
    with open(CONFIG) as f:
        m = json.load(f)["model"]
    C = int(m["kv_lora_rank"])
    contexts = [n for s in w.window_steps() for _, n in s.tokens]
    flops, nbytes = cost(w.shape.n_heads, C + int(m["qk_rope_head_dim"]),
                         C, contexts, w.shape.dtype_bytes)
    L = w.shape.n_layers
    share, _ = peaks.roofline_share(L * flops, L * nbytes, seconds, w.peak)
    return share
