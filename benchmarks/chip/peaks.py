"""The chip's peaks, and the operations and bytes the benchmark's kernels
and model step need, worked out from shapes.

Peaks are keyed by ``device.device_kind`` as JAX reports it; a kind that
is not in the table is an error, never a default.  Counts are what the
algorithm needs, not what an implementation happens to move: keys and
values of the context read once, queries and outputs once, weights of the
experts that ran once.  A roofline share built on them can only be
understated, never pushed past 100% by counting too much.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_bf16: float        # operations per second, dense bfloat16
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


V5E = Peak(
    flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
    source=("Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
            "16 GB HBM2 at 819 GB/s per chip"))

PEAKS = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak table entry for device kind {device_kind!r}; add it "
            f"to benchmarks/chip/peaks.py with its source") from None


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes the counts need, as the configuration file states them."""
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_experts: int = 0
    top_k: int = 0
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(n_layers=c["n_layers"], d_model=c["d_model"],
                   n_heads=c["n_heads"], kv_heads=c["kv_heads"],
                   head_dim=c["head_dim"], d_ff=c["d_ff"], vocab=c["vocab"],
                   n_experts=c.get("n_experts", 0), top_k=c.get("top_k", 0))

    @property
    def moe(self) -> bool:
        return self.n_experts > 0

    def matmul_params_per_token(self) -> int:
        """Weights one token multiplies by: attention projections, the
        routed experts (top_k of them) and router, or the dense MLP, and
        the output head.  The embedding is a lookup and counts nothing."""
        d, H, K, dh, f = (self.d_model, self.n_heads, self.kv_heads,
                          self.head_dim, self.d_ff)
        attn = d * H * dh + 2 * d * K * dh + H * dh * d
        ffn = (self.top_k * 3 * d * f + d * self.n_experts if self.moe
               else 3 * d * f)
        return self.n_layers * (attn + ffn) + d * self.vocab


def attention_flops(shape: Shape, context: int) -> int:
    """Scores and weighted values of one query row over ``context`` keys,
    over every layer: two multiply-adds per key per head dimension."""
    return 4 * shape.n_layers * shape.n_heads * shape.head_dim * context


def model_flops(shape: Shape, contexts: Iterable[int]) -> int:
    """Model operations for one token per entry of ``contexts`` (the
    number of keys that token attends to, itself included)."""
    per = 2 * shape.matmul_params_per_token()
    total = 0
    for c in contexts:
        total += per + attention_flops(shape, c)
    return total


def paged_attention_cost(shape: Shape, calls: Sequence[Tuple[int, int]]
                         ) -> Tuple[int, int]:
    """(operations, bytes) of one layer's paged-attention calls, summed.
    Each call is ``(start, n)``: ``n`` query rows of one sequence at
    positions ``start .. start+n-1``, each attending causally; a decode row
    is ``(length - 1, 1)``.  Bytes: the context's keys and values read
    once, the queries read and outputs written once."""
    H, K, dh, b = shape.n_heads, shape.kv_heads, shape.head_dim, \
        shape.dtype_bytes
    flops = 0
    nbytes = 0
    for start, n in calls:
        # sum over rows t of (start + t + 1) keys
        keys = n * start + n * (n + 1) // 2
        flops += 4 * H * dh * keys
        nbytes += 2 * (start + n) * K * dh * b + 2 * n * H * dh * b
    return flops, nbytes


def moe_gemm_cost(shape: Shape, routed_rows: int, experts_used: int
                  ) -> Tuple[int, int]:
    """(operations, bytes) of one grouped expert SwiGLU call: ``routed_rows``
    (token, expert) pairs through gate, up and down projections, and the
    weights of ``experts_used`` experts read once, rows in and out once."""
    d, f, b = shape.d_model, shape.d_ff, shape.dtype_bytes
    flops = routed_rows * 6 * d * f
    nbytes = experts_used * 3 * d * f * b + 2 * routed_rows * d * b
    return flops, nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Peak) -> Tuple[float, str]:
    """(share of the roofline in %, the bound: "compute" or "memory").
    The least time the chip could take is the larger of operations over
    peak rate and bytes over peak bandwidth."""
    t_flops = flops / peak.flops_bf16
    t_bytes = nbytes / peak.hbm_bytes_per_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def expected_distinct(n_experts: int, top_k: int, tokens: int) -> float:
    """Expected number of distinct experts that ``tokens`` tokens reach,
    top-k each, when routing spreads evenly over the experts."""
    if not tokens:
        return 0.0
    return n_experts * (1.0 - (1.0 - top_k / n_experts) ** tokens)
