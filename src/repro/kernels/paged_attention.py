"""Paged attention decode — Pallas TPU kernel.

The serving hot spot for guided KV tiering: one new query token per sequence
attends over KV pages scattered through the HBM pool according to a page
table.  Grid = (B, MP): the page dimension is innermost, so the per-sequence
online-softmax state (m, l, acc) lives in VMEM scratch and the output is
finalized on the last page.

The page table drives *block-index gathering*: each grid step's k/v
BlockSpec index map reads the physical pool slot for (sequence b, logical
page p) from a scalar-prefetch operand — pages never move, the kernel's
tiles jump through the pool.  Invalid / out-of-range pages contribute
nothing (masked by length).

TPU notes: pool pages are (P, K*dh) VMEM tiles (P aligned to 8 sublanes,
K*dh padded to 128 lanes by the wrapper), read without any in-kernel
reshape.  GQA rides on a block-diagonal query: the wrapper places head h's
dh lanes in its kv head's lane block and zeros elsewhere, so one
(H, K*dh) x (P, K*dh)^T matmul yields every head's scores against its own
kv head, and the (H, P) x (P, K*dh) value matmul leaves head h's output in
that same lane block, which the wrapper selects.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            page_size: int, dh: int, window: Optional[int],
            shared_table: bool):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    slot = table_ref[0 if shared_table else b, p]
    valid_page = (slot >= 0) & (p * page_size < length)

    @pl.when(valid_page)
    def _attend():
        q = q_ref[0].astype(F32)                       # (H, K*dh) block-diag
        k = k_ref[0].astype(F32)                       # (P, K*dh)
        v = v_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)   # (H, P)
        s = s * (1.0 / np.sqrt(dh))
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        ok = pos < length
        if window is not None:
            ok &= (length - 1 - pos) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]                            # (H, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=1,
                                                  keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        m_scr[...] = m_new

    @pl.when(p == np_ - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, page_table, lengths, *,
                window: Optional[int], interpret: bool, shared_table: bool):
    """q: (R,H,dh); page_table: (R,MP), or (1,MP) shared by every row."""
    R, H, dh = q.shape
    N, P, K, _ = k_pool.shape
    MP = page_table.shape[1]
    G = H // K

    # Pools flattened to (N, P, K*dh) lane-padded tiles (a free reshape
    # whenever K*dh is a multiple of 128, as at every served width).
    kd = K * dh
    kd_p = ((kd + 127) // 128) * 128
    kp = jnp.pad(k_pool.reshape(N, P, kd), ((0, 0), (0, 0), (0, kd_p - kd)))
    vp = jnp.pad(v_pool.reshape(N, P, kd), ((0, 0), (0, 0), (0, kd_p - kd)))
    # Block-diagonal query: head h keeps its dh lanes in kv head h//G's
    # block; the zeros elsewhere contribute exact zeros to every score.
    own = np.arange(K)[None, :] == (np.arange(H) // G)[:, None]   # (H, K)
    qx = jnp.where(own[None, :, :, None], q[:, :, None, :],
                   jnp.zeros((), q.dtype)).reshape(R, H, kd)
    qx = jnp.pad(qx, ((0, 0), (0, 0), (0, kd_p - kd)))

    def page_index(b, p, table, lens):
        row = 0 if shared_table else b
        return (jnp.maximum(table[row, p], 0), 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, page_size=P, dh=dh, window=window,
                          shared_table=shared_table),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # page_table, lengths
            grid=(R, MP),
            in_specs=[
                pl.BlockSpec((1, H, kd_p),
                             lambda b, p, table, lens: (b, 0, 0)),
                pl.BlockSpec((1, P, kd_p), page_index),
                pl.BlockSpec((1, P, kd_p), page_index),
            ],
            out_specs=pl.BlockSpec((1, H, kd_p),
                                   lambda b, p, table, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), F32),
                pltpu.VMEM((H, 1), F32),
                pltpu.VMEM((H, kd_p), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, H, kd_p), q.dtype),
        interpret=interpret,
    )(page_table, lengths, qx, kp, vp)
    # Head h's output is the lane block of its own kv head.
    out = out[:, :, :kd].reshape(R, H, K, dh)
    return jnp.sum(jnp.where(own[None, :, :, None], out,
                             jnp.zeros((), out.dtype)), axis=2)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_prefill_pallas(q, k_pool, v_pool, page_table, lengths,
                         window: Optional[int] = None,
                         interpret: bool = False):
    """One-shot prompt attention: S query rows of one sequence over a
    shared page table, causality via per-row ``lengths``.  The decode
    kernel with grid (S, MP) and the ONE table row every query row reads
    (scalar memory holds (1, MP), not an (S, MP) broadcast), so the
    accumulation order per row is identical to decode's and the result is
    bitwise-equal to chunked per-token ingestion.

    q: (S,H,dh); page_table: (MP,) int32; lengths: (S,) int32."""
    return _paged_call(q, k_pool, v_pool, page_table[None, :], lengths,
                       window=window, interpret=interpret, shared_table=True)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(q, k_pool, v_pool, page_table, lengths,
                           window: Optional[int] = None,
                           interpret: bool = False):
    """q: (B,H,dh); k_pool/v_pool: (N,P,K,dh); page_table: (B,MP) int32
    (-1 = unused); lengths: (B,).  Returns (B,H,dh)."""
    return _paged_call(q, k_pool, v_pool, page_table, lengths,
                       window=window, interpret=interpret, shared_table=False)
