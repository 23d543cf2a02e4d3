"""Kernel ``ssm_decode_pallas`` (``kernels/ssm_decode.py``, the Mamba-2
decode step over the recurrent state pool): its device time in the trace
against the least time the chip needs for the window's decode rows, every
Mamba layer, in %.

Per decode row and Mamba layer (``cost``): the state, H * P * N float32
values, read once and written once; the token's x (H * P) and dt (H) read
and y (H * P) written, in float32, and B and C (G * N each); operations,
two multiply-adds per state value for the update and one for the
contraction with C.  ``peaks.Shape`` has no field for the Mamba widths,
so they come from the configuration file of the one cell that runs the
kernel (``CONFIG``).  A trace without the kernel (a model without Mamba
layers, or a program without the kernel) reads nothing."""

import json
from pathlib import Path
from typing import Tuple

import peaks

KERNELS = ("ssm_decode_pallas",)
CONFIG = Path(__file__).resolve().parents[1] / "configs" / (
    "granite-4.0-h-small.10of40.ep2.json")


def cost(heads: int, head_dim: int, state: int, groups: int, rows: int
         ) -> Tuple[int, int]:
    """(operations, bytes) of one Mamba layer's decode step over ``rows``
    rows."""
    values = heads * head_dim * state
    flops = rows * 6 * values
    nbytes = rows * 4 * (2 * values + 2 * heads * head_dim + heads
                         + 2 * groups * state)
    return flops, nbytes


def read(w, suffix):
    if w.trace is None:
        return None
    seconds, calls = w.trace.kernel_seconds(KERNELS)
    if not calls or seconds <= 0:
        return None
    with open(CONFIG) as f:
        m = json.load(f)["model"]
    rows = sum(len(s.tokens) for s in w.window_steps())
    flops, nbytes = cost(int(m["mamba_n_heads"]), int(m["mamba_d_head"]),
                         int(m["mamba_d_state"]), int(m["mamba_n_groups"]),
                         rows)
    L = list(m["layer_types"]).count("mamba")
    share, _ = peaks.roofline_share(L * flops, L * nbytes, seconds, w.peak)
    return share
