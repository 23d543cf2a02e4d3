"""The peak table and the operation and byte counts, against hand counts
at small shapes."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import peaks  # noqa: E402

DENSE = peaks.Shape(n_layers=2, d_model=8, n_heads=4, kv_heads=2,
                    head_dim=2, d_ff=16, vocab=10)
MOE = peaks.Shape(n_layers=1, d_model=4, n_heads=2, kv_heads=1, head_dim=2,
                  d_ff=3, vocab=5, n_experts=4, top_k=2)


def test_v5e_peaks_and_unknown_kinds():
    p = peaks.peak_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes_per_s, p.hbm_bytes) == (
        197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError):
        peaks.peak_for("TPU v9 imaginary")


def test_matmul_params_per_token_by_hand():
    # attention: 8*4*2 (q) + 2 * 8*2*2 (k, v) + 4*2*8 (o) = 64+64+64 = 192
    # mlp: 3*8*16 = 384; two layers; head 8*10
    assert DENSE.matmul_params_per_token() == 2 * (192 + 384) + 80
    # attention 4*2*2 + 2*4*1*2 + 2*2*4 = 16+16+16 = 48; experts 2*3*4*3 =
    # 72; router 4*4 = 16; head 4*5 = 20
    assert MOE.matmul_params_per_token() == 48 + 72 + 16 + 20


def test_model_flops_adds_attention_over_the_context():
    per = 2 * DENSE.matmul_params_per_token()
    # attention per key: 4 * layers * heads * head_dim = 4*2*4*2 = 64
    assert peaks.model_flops(DENSE, [3, 5]) == 2 * per + 64 * (3 + 5)


def test_paged_attention_cost_by_hand():
    # decode row over 5 keys: 4*H*dh*5 = 4*4*2*5 = 160 flops; bytes: keys
    # and values 2*5*K*dh*2 = 80, query and output 2*1*H*dh*2 = 32
    assert peaks.paged_attention_cost(DENSE, [(4, 1)]) == (160, 112)
    # 3 prompt rows from position 2: keys 3+4+5 = 12 -> 4*4*2*12 = 384;
    # bytes 2*5*2*2*2 = 80 + 2*3*4*2*2 = 96
    assert peaks.paged_attention_cost(DENSE, [(2, 3)]) == (384, 176)


def test_moe_gemm_cost_by_hand():
    # 6 routed rows * 6*d*f = 6*6*4*3 = 432; weights 2 experts * 3*4*3*2
    # = 144 bytes, rows in and out 2*6*4*2 = 96
    assert peaks.moe_gemm_cost(MOE, 6, 2) == (432, 240)


def test_roofline_share_takes_the_binding_bound():
    p = peaks.V5E
    share, bound = peaks.roofline_share(197e12, 1.0, 2.0, p)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = peaks.roofline_share(1.0, 819e9, 4.0, p)
    assert bound == "memory" and share == pytest.approx(25.0)


def test_expected_distinct_experts():
    assert peaks.expected_distinct(40, 8, 0) == 0.0
    assert peaks.expected_distinct(40, 8, 1) == pytest.approx(8.0)
    assert 39.9 < peaks.expected_distinct(40, 8, 32) <= 40.0
