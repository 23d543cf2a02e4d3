"""The serving kernels compile for a TPU v5e at granite-moe-3b-a800m's
and DeepSeek-V2-Lite's widths — checked here, without the chip, against a
described v5e topology.

Each kernel test compiles one Pallas kernel at the shapes the chip run
serves and asserts the compiled program holds the Mosaic kernel
(``tpu_custom_call``).  Interpret-mode tests cannot see what Mosaic refuses
(unaligned reshapes, scalar-memory and VMEM limits); this can.  The engine
tests compile the tiered layer pieces at the chip cells' pool shapes and
assert that none copies a whole pool (the pools are donated).  The
topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gemm import moe_grouped_ffn_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_mla_decode_pallas,
    paged_prefill_pallas,
)

# granite-moe-3b-a800m: 24 query heads, 8 KV heads of 64, d_model 1536,
# 40 experts of width 512, top-8; 16-token pages, 132 pages per sequence.
B, H, K, DH = 8, 24, 8, 64
N_PAGES, PAGE, MP = 1024, 16, 132
S = 2048
D, F, E, TOP_K = 1536, 512, 40, 8
BF16, I32 = jnp.bfloat16, jnp.int32
# DeepSeek-V2-Lite: 16 heads, a cached latent of 512 + 64, query and key
# heads of 128 + 64, value heads of 128; 544 pages per sequence.
MLA_H, MLA_L, MLA_C, MLA_QK, MLA_MP = 16, 576, 512, 192, 544
MLA_SCALE = 1.5896 / 192 ** 0.5
# Its experts: width 1,408 (11 x 128 lanes) at d_model 2,048, a cache of
# 192 blocks, a decode batch of 32 rows with 6 picks each.
MLA_D, MLA_F, MLA_SLOTS, MLA_E = 2048, 1408, 192, 64


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


def test_paged_mla_decode_compiles_for_v5e(one_chip):
    text = _compiled_text(
        functools.partial(paged_mla_decode_pallas, scale=MLA_SCALE,
                          dv=MLA_C), one_chip,
        ((32, MLA_H, MLA_L), BF16), ((N_PAGES, PAGE, MLA_L), BF16),
        ((32, MLA_MP), I32), ((32,), I32))
    assert "tpu_custom_call" in text


def test_flash_attention_mla_prefill_compiles_for_v5e(one_chip):
    """The latent model's plain prefill: query block as long as the table's
    context plus the bucket, heads of 192, the model's softmax scale."""
    T = MLA_MP * PAGE + S
    text = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, True, None, False,
                                               MLA_SCALE), one_chip,
        ((1, T, MLA_H, MLA_QK), BF16), ((1, T, MLA_H, MLA_QK), BF16),
        ((1, T, MLA_H, MLA_QK), BF16))
    assert "tpu_custom_call" in text


def test_moe_grouped_ffn_compiles_at_deepseek_widths_without_padding(
        one_chip):
    """At d 2,048 and width 1,408 the kernel fits VMEM, and no pad copies
    the 192-block expert cache on each call."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                ((32 * 6, MLA_D), BF16), ((MLA_SLOTS, MLA_D, MLA_F), BF16),
                ((MLA_SLOTS, MLA_D, MLA_F), BF16),
                ((MLA_SLOTS, MLA_F, MLA_D), BF16), ((MLA_E,), I32),
                ((MLA_E,), I32))]
    compiled = jax.jit(moe_grouped_ffn_pallas).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    cache = MLA_SLOTS * MLA_D * MLA_F * 2
    assert compiled.memory_analysis().temp_size_in_bytes < cache // 8


# The chip cells' engines: model overrides, pool shape (layers, pages,
# page, [KV heads,] row) and arrays, decode batch, pages per sequence.
ENGINES = {
    "granite_moe_3b_a800m": ({}, (32, 2560, PAGE, K, DH), 2, 32, 160),
    "deepseek_v2_lite": ({"n_layers": 7}, (7, 12288, PAGE, MLA_L), 1, 32,
                         MLA_MP),
}
PREFILL_BUCKET = 512


def _tiered_engine(arch, overrides):
    """An engine holding only the model and its tiered layer pieces: the
    pieces read nothing else, and the constructor would allocate the pools
    and the weights."""
    from repro.configs import get
    from repro.models import build_model
    from repro.serve.engine import Engine

    engine = Engine.__new__(Engine)
    engine.model = build_model(
        dataclasses.replace(get(arch), remat=False, **overrides))
    engine._build_tiered_closures()
    return engine


def _copies_of(text, shape):
    """The compiled entry's ``copy``/``copy-start`` instructions whose
    result holds an array of ``shape``."""
    dims = "[" + ",".join(map(str, shape)) + "]"
    return [line.strip() for line in text.splitlines()
            if (m := re.search(r"= (.+?) copy(?:-start)?\(", line))
            and dims in m.group(1)]


@pytest.mark.parametrize("dispatch", ["decode", "prefill"])
@pytest.mark.parametrize("arch", sorted(ENGINES))
def test_tiered_piece_updates_the_pools_in_place_for_v5e(
        one_chip, monkeypatch, arch, dispatch):
    """The attention piece, for either dispatch kind, takes the pools
    donated and writes one layer's rows in place: no copy of a whole pool
    (1.34 GB per array at granite's cell, 1.59 GB at DeepSeek-V2-Lite's) on
    each of a step's layer calls."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_MODE", "pallas")   # the chip's kernels
    overrides, pool, n_arrays, B, mp = ENGINES[arch]
    engine = _tiered_engine(arch, overrides)
    model = engine.model
    dt, d = model.cfg.dtype, model.cfg.d_model

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype),
                                    model.abstract())
    layers = dict(params["layers"])          # the served view: experts
    layers["moe"] = {"router": layers["moe"]["router"]}   # live off-chip
    params = {**params, "layers": layers}
    pools = tuple(sd(pool, dt) for _ in range(n_arrays))
    if dispatch == "decode":
        rows = [sd((B,), I32)] * 3 + [sd((B,), jnp.bool_)]
        args = (sd((B, 1, d), dt), sd((), I32), sd((B, mp), I32), *rows)
    else:
        S = PREFILL_BUCKET
        rows = [sd((S,), I32)] * 4 + [sd((S,), jnp.bool_)]
        args = (sd((1, S, d), dt), sd((), I32), sd((mp,), I32), *rows)
    text = engine._t_attn.lower(params, pools, *args,
                                dispatch=dispatch).compile().as_text()
    assert "tpu_custom_call" in text
    assert not _copies_of(text, pool), _copies_of(text, pool)


# granite-4.0-h-small as its cell serves it: stage 1 of 4 (layers 0-9:
# nine Mamba-2 layers, attention at index 5), experts 0-35 of 72 held; 64
# decode rows; the pools: 20,480 pages of the one attention layer's K and
# V, each token's 8 heads of 128 one flat row, and the recurrent state of
# 65 slots (64 rows and the scratch): the conv's last 3 inputs of 8,448
# lanes, and 128 x (128 heads x 64) float32 SSM values.
HYBRID_ROWS, HYBRID_MP, HYBRID_BUCKET = 64, 640, 2048
HYBRID_POOLS = (((1, 20480, PAGE, 1024), BF16), ((1, 20480, PAGE, 1024), BF16),
                ((9, 65, 3 * 8448), BF16), ((9, 65, 128, 8192), jnp.float32))


def test_ssm_decode_compiles_for_v5e_and_updates_the_pool_in_place(
        one_chip):
    from repro.kernels.ssm_decode import ssm_decode_pallas

    R, F32 = HYBRID_ROWS, jnp.float32
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                HYBRID_POOLS[3], ((), I32), ((R,), I32), ((R, 8192), BF16),
                ((R, 128), F32), ((128,), F32), ((128,), F32),
                ((R, 1, 128), F32), ((R, 1, 128), F32))]
    compiled = jax.jit(ssm_decode_pallas, donate_argnums=0).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _copies_of(text, HYBRID_POOLS[3][0])
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("dispatch", ["decode", "prefill"])
def test_hybrid_programs_update_every_pool_in_place_for_v5e(
        one_chip, monkeypatch, dispatch):
    """The resident decode step and the largest prefill dispatch of the
    granite-4.0-H cell take the K/V pools and the state pool donated and
    copy none of them whole (1.34 GB of K/V, 2.45 GB of state), and fit
    the chip beside the weights."""
    from repro.configs import get
    from repro.kernels import ops
    from repro.models import build_model
    from repro.serve.engine import Engine, _jit

    monkeypatch.setattr(ops, "_MODE", "pallas")
    full = get("granite_4_0_h_small")
    engine = Engine.__new__(Engine)
    engine.model = build_model(dataclasses.replace(
        full, remat=False, n_layers=10, layer_types=full.layer_types[:10],
        n_local_experts=36))

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype),
                                    engine.model.abstract())
    pools = tuple(sd(*p) for p in HYBRID_POOLS)
    if dispatch == "decode":
        B, F32 = HYBRID_ROWS, jnp.float32
        fn = functools.partial(engine._decode_scan, False)
        args = (sd((B, 1), I32), sd((B, HYBRID_MP), I32),
                *[sd((B,), I32)] * 3, sd((B,), jnp.bool_), sd((B,), I32),
                sd((B,), F32), sd((B,), I32), sd((B,), F32), sd((B,), I32))
    else:
        S = HYBRID_BUCKET
        fn = engine._prefill_scan
        args = (sd((S,), I32), sd((HYBRID_MP,), I32), sd((S,), I32),
                sd((S,), I32), sd((), I32), sd((), I32), sd((), I32))
    compiled = _jit(fn, donate_argnums=1).lower(params, pools,
                                                *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for shape, _ in HYBRID_POOLS:
        assert not _copies_of(text, shape), _copies_of(text, shape)
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert held < 16e9
