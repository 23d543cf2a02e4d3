"""The serving kernels compile for a TPU v5e at granite-moe-3b-a800m's
widths — checked here, without the chip, against a described v5e topology.

Each test compiles one Pallas kernel at the shapes the chip run serves and
asserts the compiled program holds the Mosaic kernel (``tpu_custom_call``).
Interpret-mode tests cannot see what Mosaic refuses (unaligned reshapes,
scalar-memory and VMEM limits); this can.  The topology is described inside
a module fixture, never at import, so every test worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.moe_gemm import moe_grouped_ffn_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_prefill_pallas,
)

# granite-moe-3b-a800m: 24 query heads, 8 KV heads of 64, d_model 1536,
# 40 experts of width 512, top-8; 16-token pages, 132 pages per sequence.
B, H, K, DH = 8, 24, 8, 64
N_PAGES, PAGE, MP = 1024, 16, 132
S = 2048
D, F, E, TOP_K = 1536, 512, 40, 8
BF16, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    # check: disable=EXC01 -- the skip reports it: without a TPU compiler
    # there is nothing to check here.
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paged_attention_decode_compiles_for_v5e(one_chip):
    text = _compiled_text(
        paged_attention_pallas, one_chip,
        ((B, H, DH), BF16), ((N_PAGES, PAGE, K, DH), BF16),
        ((N_PAGES, PAGE, K, DH), BF16), ((B, MP), I32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_paged_prefill_compiles_for_v5e(one_chip):
    text = _compiled_text(
        paged_prefill_pallas, one_chip,
        ((S, H, DH), BF16), ((N_PAGES, PAGE, K, DH), BF16),
        ((N_PAGES, PAGE, K, DH), BF16), ((MP,), I32), ((S,), I32))
    assert "tpu_custom_call" in text


def test_moe_grouped_ffn_compiles_for_v5e(one_chip):
    text = _compiled_text(
        moe_grouped_ffn_pallas, one_chip,
        ((S * TOP_K, D), BF16), ((E, D, F), BF16), ((E, D, F), BF16),
        ((E, F, D), BF16), ((E,), I32))
    assert "tpu_custom_call" in text
