"""Paged attention decode — Pallas TPU kernels.

The serving hot spot for guided KV tiering: one new query token per sequence
attends over KV pages scattered through the HBM pool according to a page
table.

The GQA kernel walks a row's pages a block at a time.  Grid = (R,): each
grid step loops over its row's blocks up to the row's length, so nothing
past a row's last page is copied, computed or stepped over.  Each block
gathers ``ppb`` pages of K and of V from their table slots into VMEM by
DMA (the pools stay in HBM, ``memory_space=ANY``), one copy per page, and
runs one score matmul, one online-softmax update and one value matmul
over the whole (ppb*P, K*dh) block.  The copies are double-buffered: the
loop starts the next block's before it computes its own.  Inside a row's
last block only the pages that hold a position below the length and have
a slot are copied, and every other position is masked (values too, since
the buffer holds stale bytes there).  The online-softmax state (m, l,
acc) lives in VMEM scratch; a row of length 0 runs no block and returns
zeros.

``ppb`` follows from the page size, the table's width and the tile alone
(``_block_pages``), never from the row count, so decode and prefill block a
table alike and a one-shot prefill stays bitwise-equal to stepping its
tokens through decode.

TPU notes: pool pages are (P, K*dh) tiles (P aligned to 8 sublanes, K*dh
padded to 128 lanes by the wrapper), read without any in-kernel reshape
but the merge of a block's pages.  GQA rides on a block-diagonal query:
the wrapper places head h's dh lanes in its kv head's lane block and zeros
elsewhere, so one (H, K*dh) x (ppb*P, K*dh)^T matmul yields every head's
scores against its own kv head, and the (H, ppb*P) x (ppb*P, K*dh) value
matmul leaves head h's output in that same lane block, which the wrapper
selects.

Absorbed multi-head latent attention (``paged_mla_decode_pallas``) walks
ONE latent pool a page per grid step (grid = (R, MP)), each page's tile
gathered by its BlockSpec index map from the scalar-prefetched table:
every head's absorbed query (H, L) scores against the page's (P, L) latent
rows with the model's softmax scale, and the values are the latent's first
``dv`` lanes of the same tile, so each page is read once for keys and
values both.
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
# Tokens a GQA block gathers, and the most bytes its float32 key tile
# may take.
BLOCK_TOKENS = 128
BLOCK_BYTES = 512 << 10


def _block_pages(page_size: int, max_pages: int, lanes: int) -> int:
    """Pages per GQA block: about ``BLOCK_TOKENS`` tokens, fewer where
    a block's float32 (tokens, ``lanes``) tile would pass ``BLOCK_BYTES``,
    and no more than the table holds."""
    tokens = min(BLOCK_TOKENS, BLOCK_BYTES // (4 * lanes))
    return max(1, min(max_pages, tokens // page_size))


def _online_update(s, v, m_scr, l_scr, acc_scr):
    """Fold masked scores ``s`` (H, T) and values ``v`` (T, C) into the
    running max, normalizer and accumulator."""
    m_prev = m_scr[...]                                # (H, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    pexp = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
    m_scr[...] = m_new


def _init_state(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr):
    l = l_scr[...]
    safe = jnp.where(l > 0, l, 1.0)
    o_ref[0] = (acc_scr[...] / safe).astype(o_ref.dtype)


def _kernel(table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, m_scr, l_scr, acc_scr, *,
            page_size: int, ppb: int, scale: float,
            window: Optional[int], shared_table: bool, n_blocks: int):
    """One row of the GQA walk: a loop over the row's blocks up to its
    length (at most ``n_blocks``), each block's copies started before the
    block ahead of it is computed."""
    b = pl.program_id(0)
    bk = ppb * page_size
    length = len_ref[b]
    n = jnp.minimum((length + bk - 1) // bk, n_blocks)

    def slot_of(page):
        return table_ref[0 if shared_table else b, page]

    def gather(blk, buf, wait: bool):
        """Start, or wait for, the copies of block ``blk`` into buffer
        ``buf``: one per page that holds a position below the length and
        has a slot."""
        for j in range(ppb):
            page = blk * ppb + j
            slot = slot_of(page)

            @pl.when((slot >= 0) & (page * page_size < length))
            def _copy():
                for hbm, vmem in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    copy = pltpu.make_async_copy(
                        hbm.at[slot], vmem.at[buf, j], sem.at[buf])
                    if wait:
                        copy.wait()
                    else:
                        copy.start()

    _init_state(m_scr, l_scr, acc_scr)

    @pl.when(n > 0)
    def _first():
        gather(0, 0, wait=False)

    def body(i, carry):
        cur = i % 2

        @pl.when(i + 1 < n)
        def _next():
            gather(i + 1, 1 - cur, wait=False)

        gather(i, cur, wait=True)
        lanes = k_buf.shape[-1]
        k = k_buf[cur].astype(F32).reshape(bk, lanes)  # (ppb*P, K*dh)
        v = v_buf[cur].astype(F32).reshape(bk, lanes)
        q = q_ref[0].astype(F32)                       # (H, K*dh) block-diag

        def visible(axis):
            """Positions of the block the row sees, along ``axis``."""
            shape = (1, bk) if axis == 1 else (bk, 1)
            off = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
            pos = i * bk + off
            ok = pos < length
            if window is not None:
                ok &= (length - 1 - pos) < window
            has_slot = jnp.zeros(shape, jnp.bool_)
            for j in range(ppb):
                in_page = (off >= j * page_size) & (off < (j + 1) * page_size)
                has_slot |= in_page & (slot_of(i * ppb + j) >= 0)
            return ok & has_slot

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)   # (H, ppb*P)
        s = jnp.where(visible(1), s * scale, NEG_INF)
        # Unseen positions may hold stale bytes, even NaNs: zero them so
        # their zero weights contribute exact zeros.
        v = jnp.where(visible(0), v, 0.0)
        _online_update(s, v, m_scr, l_scr, acc_scr)
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    _finalize(o_ref, l_scr, acc_scr)


def _mla_kernel(table_ref, len_ref, q_ref, kv_ref, o_ref,
                m_scr, l_scr, acc_scr, *,
                page_size: int, scale: float, dv: int):
    """One (row, page) grid step of the latent walk: the page's (P, L)
    tile holds the keys, and its first ``dv`` lanes the values."""
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        _init_state(m_scr, l_scr, acc_scr)

    length = len_ref[b]
    slot = table_ref[b, p]
    valid_page = (slot >= 0) & (p * page_size < length)

    @pl.when(valid_page)
    def _attend():
        q = q_ref[0].astype(F32)                       # (H, L)
        k = kv_ref[0].astype(F32)                      # (P, L)
        v = k[:, :dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)   # (H, P)
        s = s * scale
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        _online_update(s, v, m_scr, l_scr, acc_scr)

    @pl.when(p == np_ - 1)
    def _done():
        _finalize(o_ref, l_scr, acc_scr)


def _paged_call(q, k_pool, v_pool, page_table, lengths, *,
                window: Optional[int], interpret: bool, shared_table: bool,
                scale: Optional[float]):
    """q: (R,H,dh); pools (N,P,K,dh) or already flat (N,P,K*dh);
    page_table: (R,MP), or (1,MP) shared by every row."""
    R, H, dh = q.shape
    N, P = k_pool.shape[:2]
    K = k_pool.shape[-1] // dh if k_pool.ndim == 3 else k_pool.shape[2]
    MP = page_table.shape[1]
    G = H // K
    if scale is None:
        scale = 1.0 / np.sqrt(dh)

    # Pools flattened to (N, P, K*dh) lane-padded tiles.  The reshape of a
    # (N, P, K, dh) pool changes its tiled layout, so XLA copies it; a
    # pool kept flat is read as it lies.
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
    ppb = _block_pages(P, MP, kd_p)
    if MP % ppb:
        page_table = jnp.pad(page_table, ((0, 0), (0, -MP % ppb)),
                             constant_values=-1)

    def row_block(b, table, lens):
        return (b, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, page_size=P, ppb=ppb, scale=scale,
                          window=window, shared_table=shared_table,
                          n_blocks=page_table.shape[1] // ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # page_table, lengths
            grid=(R,),
            in_specs=[
                pl.BlockSpec((1, H, kd_p), row_block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, kd_p), row_block),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, P, kd_p), kp.dtype),
                pltpu.VMEM((2, ppb, P, kd_p), vp.dtype),
                pltpu.SemaphoreType.DMA((2,)),
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
    jax.jit, static_argnames=("window", "interpret", "scale"))
def paged_prefill_pallas(q, k_pool, v_pool, page_table, lengths,
                         window: Optional[int] = None,
                         interpret: bool = False,
                         scale: Optional[float] = None):
    """One-shot prompt attention: S query rows of one sequence over a
    shared page table, causality via per-row ``lengths``.  The decode
    kernel over S rows and the ONE table row every query row reads
    (scalar memory holds (1, MP), not an (S, MP) broadcast), blocked as
    decode blocks it, so the accumulation order per row is identical to
    decode's and the result is bitwise-equal to chunked per-token
    ingestion.

    q: (S,H,dh); page_table: (MP,) int32; lengths: (S,) int32."""
    return _paged_call(q, k_pool, v_pool, page_table[None, :], lengths,
                       window=window, interpret=interpret, shared_table=True,
                       scale=scale)


@functools.partial(
    jax.jit, static_argnames=("window", "interpret", "scale"))
def paged_attention_pallas(q, k_pool, v_pool, page_table, lengths,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           scale: Optional[float] = None):
    """q: (B,H,dh); k_pool/v_pool: (N,P,K,dh), or flat (N,P,K*dh);
    page_table: (B,MP) int32 (-1 = unused); lengths: (B,).  Scores are
    scaled by ``scale`` (default 1/sqrt(dh)).  Returns (B,H,dh)."""
    return _paged_call(q, k_pool, v_pool, page_table, lengths,
                       window=window, interpret=interpret, shared_table=False,
                       scale=scale)


@functools.partial(
    jax.jit, static_argnames=("scale", "dv", "interpret"))
def paged_mla_decode_pallas(q, pool, page_table, lengths, scale: float,
                            dv: int, interpret: bool = False):
    """Absorbed MLA decode: q (R, H, L) absorbed queries; pool (N, P, L)
    one layer's latent pages; page_table (R, MP) int32 (-1 = unused);
    lengths (R,).  Scores q.latent * ``scale``; values the latent's first
    ``dv`` lanes.  Returns (R, H, dv).

    The pool's tiles are its whole (P, L) rows, so no lane padding (and no
    copy of the pool) is needed at L = 576."""
    R, H, L = q.shape
    P = pool.shape[1]
    MP = page_table.shape[1]

    def page_index(b, p, table, lens):
        return (jnp.maximum(table[b, p], 0), 0, 0)

    return pl.pallas_call(
        functools.partial(_mla_kernel, page_size=P, scale=scale, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,      # page_table, lengths
            grid=(R, MP),
            in_specs=[
                pl.BlockSpec((1, H, L),
                             lambda b, p, table, lens: (b, 0, 0)),
                pl.BlockSpec((1, P, L), page_index),
            ],
            out_specs=pl.BlockSpec((1, H, dv),
                                   lambda b, p, table, lens: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), F32),
                pltpu.VMEM((H, 1), F32),
                pltpu.VMEM((H, dv), F32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, H, dv), q.dtype),
        interpret=interpret,
    )(page_table, lengths, q, pool)
