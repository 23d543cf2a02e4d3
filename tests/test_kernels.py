"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    paged_attention_pallas,
    paged_prefill_pallas,
)
from repro.kernels.ssd_scan import ssd_scan_pallas

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,K,dh,causal,window",
    [
        (2, 128, 128, 4, 4, 64, True, None),      # MHA causal
        (1, 256, 256, 8, 2, 64, True, None),      # GQA
        (2, 128, 128, 4, 2, 128, True, 64),       # sliding window
        (1, 128, 256, 4, 4, 64, True, None),      # Sk > Sq (continuation)
        (2, 96, 96, 4, 4, 80, True, None),        # unaligned seq + dh
        (1, 128, 128, 4, 4, 64, False, None),     # bidirectional (encoder)
    ],
)
def test_flash_attention_sweep(B, Sq, Sk, H, K, dh, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, Sq, H, dh), dtype)
    k = rand(ks[1], (B, Sk, K, dh), dtype)
    v = rand(ks[2], (B, Sk, K, dh), dtype)
    got = flash_attention_pallas(q, k, v, causal, window, True)
    want = ref.mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_grad_matches_reference():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 128, 4, 64), jnp.float32)
    k = rand(ks[1], (1, 128, 2, 64), jnp.float32)
    v = rand(ks[2], (1, 128, 2, 64), jnp.float32)

    def loss_kernel(q, k, v):
        return flash_attention_pallas(q, k, v, True, None, True).sum()

    def loss_ref(q, k, v):
        return ref.mha_reference(q, k, v, causal=True).sum()

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


# --------------------------------------------------------- paged attention
# The TPU interpreter: scratch memory starts as NaNs and a DMA lands only
# when it is waited for, so a stale buffer or a missed wait shows.
TPU_INTERPRET = pltpu.InterpretParams()


def _case(B, H, K, dh, N, P, MP, lens, flat, dtype, rng):
    """Queries, pools and a table for ``lens`` (None: random lengths).
    Given lengths also hold one page past the length where the row has
    room, which the walk must not read."""
    q = jnp.asarray(rng.normal(size=(B, H, dh)), dtype)
    pool = (N, P, K * dh) if flat else (N, P, K, dh)
    kp = jnp.asarray(rng.normal(size=pool), dtype)
    vp = jnp.asarray(rng.normal(size=pool), dtype)
    table = np.full((B, MP), -1, np.int32)
    lengths = np.zeros((B,), np.int32)
    slots = rng.permutation(N)
    si = 0
    for b in range(B):
        if lens is None:
            n_pages = int(rng.integers(1, MP + 1))
            lengths[b] = int(rng.integers((n_pages - 1) * P + 1,
                                          n_pages * P + 1))
        else:
            lengths[b] = lens[b]
            n_pages = min(MP, -(-lens[b] // P) + 1)
        for pg in range(n_pages):
            table[b, pg] = slots[si]
            si += 1
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lengths)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,K,dh,N,P,MP,window,lens,flat",
    [
        pytest.param(2, 8, 8, 64, 8, 8, 3, None, None, False,
                     id="2-8-8-64-8-8-3-None"),           # MHA
        pytest.param(3, 8, 4, 64, 16, 8, 4, None, None, False,
                     id="3-8-4-64-16-8-4-None"),          # GQA
        pytest.param(2, 4, 4, 128, 8, 16, 2, None, None, False,
                     id="2-4-4-128-8-16-2-None"),         # bigger pages
        pytest.param(2, 8, 4, 64, 16, 8, 4, 7, None, False,
                     id="2-8-4-64-16-8-4-7"),             # sliding window
        pytest.param(1, 8, 2, 96, 8, 8, 4, None, None, False,
                     id="1-8-2-96-8-8-4-None"),           # unaligned dh
        # 24 pages of 16 in three blocks of eight: rows that end mid-block,
        # on a block's end, at 0, at the table's end and in the first page.
        pytest.param(5, 8, 2, 64, 64, 16, 24, None,
                     (200, 128, 0, 384, 1), False, id="blocks"),
        # 20 pages: the table padded to three blocks; a window across
        # blocks.
        pytest.param(4, 8, 2, 64, 64, 16, 20, 40, (300, 0, 256, 17), False,
                     id="blocks-padded-window"),
        # granite-4.0-H's tile: a flat pool, 8 KV heads of 128.
        pytest.param(2, 32, 8, 128, 48, 16, 24, None, (130, 384), True,
                     id="flat-kv8-dh128"),
        # granite's tile: an (N, P, K, dh) pool, 8 KV heads of 64.
        pytest.param(3, 24, 8, 64, 48, 16, 20, None, (129, 0, 320), False,
                     id="kv8-dh64"),
    ],
)
def test_paged_attention_sweep(B, H, K, dh, N, P, MP, window, lens, flat,
                               dtype):
    q, kp, vp, table, lengths = _case(B, H, K, dh, N, P, MP, lens, flat,
                                      dtype, np.random.default_rng(0))
    got = np.asarray(
        paged_attention_pallas(q, kp, vp, table, lengths, window=window,
                               interpret=TPU_INTERPRET), np.float32)
    want = np.asarray(
        ref.paged_attention_reference(q, kp, vp, table, lengths,
                                      window=window), np.float32)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live],
                               atol=TOL[dtype], rtol=TOL[dtype])
    # A row of length 0 sees nothing: zeros, as padded prefill rows need.
    assert np.array_equal(got[~live], np.zeros_like(got[~live]))


def test_paged_prefill_equals_decode_bitwise():
    """S prompt rows in one prefill call equal S one-row decode calls at
    lengths 1..S, bit for bit, over a table of three blocks whose rows
    reach the second."""
    rng = np.random.default_rng(5)
    S, N, P, K, dh, MP = 140, 32, 16, 2, 64, 24
    q, kp, vp, table = _prefill_case(rng, S, N, P, K, dh, MP)
    lengths = jnp.arange(1, S + 1, dtype=jnp.int32)
    got = paged_prefill_pallas(q, kp, vp, table, lengths, interpret=True)
    for t in range(S):
        one = paged_attention_pallas(q[t:t + 1], kp, vp, table[None, :],
                                     lengths[t:t + 1], interpret=True)
        assert np.array_equal(np.asarray(one[0]), np.asarray(got[t])), t


# ----------------------------------------------------- one-shot prefill
def _prefill_case(rng, S, N, P, K, dh, MP):
    q = jnp.asarray(rng.normal(size=(S, 4 * K, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(N, P, K, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, P, K, dh)), jnp.float32)
    table = np.full((MP,), -1, np.int32)
    slots = rng.permutation(N)
    for pg in range(-(-S // P)):
        table[pg] = slots[pg]
    return q, kp, vp, jnp.asarray(table)


@pytest.mark.parametrize("window", [None, 5])
def test_paged_prefill_sweep(window):
    """One sequence's S prompt rows with causal lengths 1..S (plus padded
    zero-length rows): Pallas vs the masked-einsum oracle."""
    rng = np.random.default_rng(3)
    S, N, P, K, dh, MP = 11, 8, 4, 2, 64, 4
    q, kp, vp, table = _prefill_case(rng, S, N, P, K, dh, MP)
    lengths = jnp.asarray(
        np.concatenate([np.arange(1, S + 1), np.zeros(5)]).astype(np.int32))
    qpad = jnp.concatenate([q, jnp.zeros((5,) + q.shape[1:], q.dtype)])
    got = paged_prefill_pallas(qpad, kp, vp, table, lengths, window=window,
                               interpret=True)
    want = ref.paged_prefill_reference(qpad, kp, vp, table, lengths,
                                       window=window)
    np.testing.assert_allclose(
        np.asarray(got[:S], np.float32), np.asarray(want[:S], np.float32),
        atol=TOL[jnp.float32], rtol=TOL[jnp.float32])
    assert np.all(np.isfinite(np.asarray(got))), \
        "padded zero-length rows must not emit NaNs"


def test_paged_prefill_padding_invariance():
    """Bucketed prompts: real rows of a padded call must be bitwise equal
    to the unpadded call — the property that lets the engine pad prompts
    to power-of-two buckets without perturbing ingestion."""
    rng = np.random.default_rng(4)
    S, N, P, K, dh, MP = 7, 8, 4, 2, 64, 4
    q, kp, vp, table = _prefill_case(rng, S, N, P, K, dh, MP)
    lengths = jnp.asarray(np.arange(1, S + 1, dtype=np.int32))
    exact = ref.paged_prefill_reference(q, kp, vp, table, lengths)
    qpad = jnp.concatenate([q, jnp.zeros((9,) + q.shape[1:], q.dtype)])
    lpad = jnp.concatenate([lengths, jnp.zeros((9,), jnp.int32)])
    padded = ref.paged_prefill_reference(qpad, kp, vp, table, lpad)
    assert np.array_equal(np.asarray(padded[:S]), np.asarray(exact))


# ----------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Q,H,P,N,bh",
    [
        (2, 64, 8, 32, 16, 4),
        (1, 128, 4, 64, 64, 4),
        (2, 128, 16, 64, 64, 8),
        (1, 64, 2, 64, 32, 2),
    ],
)
def test_ssd_scan_sweep(B, Q, H, P, N, bh, dtype):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(B, Q, H, P)), dtype)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, Q, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, Q, N)), dtype)
    Cm = jnp.asarray(rng.normal(size=(B, Q, N)), dtype)
    got = ssd_scan_pallas(x, dt, A, Bm, Cm, block_h=bh, interpret=True)
    want = ref.ssd_reference(x, dt, A, Bm, Cm)
    scale = float(jnp.abs(want).max()) + 1e-6
    np.testing.assert_allclose(
        np.asarray(got) / scale, np.asarray(want) / scale,
        atol=5 * TOL[dtype], rtol=5 * TOL[dtype])


def test_ssd_kernel_agrees_with_model_chunk():
    """The kernel computes exactly the intra-chunk term of models/ssm.py's
    chunked scan (single chunk, zero initial state)."""
    from repro.models.ssm import SSMConfig, ssd_chunked

    rng = np.random.default_rng(3)
    B, Q, H, P, N = 1, 128, 4, 32, 16
    cfg = SSMConfig(d_model=8, d_inner=H * P, head_dim=P, state_dim=N,
                    chunk=Q)
    x = jnp.asarray(rng.normal(size=(B, Q, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(B, Q, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, Q, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, Q, N)), jnp.float32)
    # The model's scan takes B and C by group: one group here.
    y_model, _ = ssd_chunked(x, dt, A, Bm[:, :, None], Cm[:, :, None], cfg)
    y_kernel = ssd_scan_pallas(x, dt, A, Bm, Cm, interpret=True)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------- ops dispatch
def test_ops_mode_dispatch():
    assert ops.current_mode() in ("reference", "pallas")
    ops.set_mode("interpret")
    try:
        q = jnp.ones((1, 128, 4, 64), jnp.float32)
        k = jnp.ones((1, 128, 4, 64), jnp.float32)
        out = ops.flash_attention(q, k, q)
        want = ref.mha_reference(q, k, q)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-5)
    finally:
        ops.set_mode(None)
