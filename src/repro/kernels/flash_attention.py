"""Flash attention forward — Pallas TPU kernel.

Blockwise online-softmax attention with GQA, causal and sliding-window
masking.  Grid = (batch*q_heads, q_blocks, kv_blocks); the kv dimension is
innermost so the (m, l, acc) running state lives in VMEM scratch across kv
steps and the output block is written once on the last step.

Tiling: q/k/v blocks are (block_q, head_dim) / (block_k, head_dim) VMEM
tiles; head_dim is padded to a multiple of 128 by the wrapper (MXU lane
alignment), and scores accumulate in f32 regardless of input dtype.

Backward: ``flash_attention_pallas`` carries a custom VJP whose backward
pass recomputes attention with the pure-jnp oracle (exact, O(S^2/blocks)
memory via the same chunking) — the fwd kernel is the serving/prefill hot
spot; a fused Pallas backward is an optimization left on the table and
noted in EXPERIMENTS.md.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

F32 = jnp.float32
NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            block_q: int, block_k: int, seq_k: int, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(F32)            # (block_q, dh)
    k = k_ref[0].astype(F32)            # (block_k, dh)
    v = v_ref[0].astype(F32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=F32) * scale        # (block_q, block_k)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < seq_k                 # padding guard
    if causal:
        mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=F32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret",
                     "scale"))
def flash_attention_fwd(q, k, v, causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False,
                        scale: Optional[float] = None):
    """q: (B,Sq,H,dh); k/v: (B,Sk,K,dh) with H = G*K.  Returns (B,Sq,H,dh).
    ``scale`` defaults to 1/sqrt(dh)."""
    B, Sq, H, dh = q.shape
    _, Sk, K, _ = k.shape
    G = H // K
    if scale is None:
        scale = 1.0 / np.sqrt(dh)

    # Layout: fold heads into the grid's leading dim; pad dh to lanes.
    dh_p = ((dh + 127) // 128) * 128
    qt = _pad_to(q.transpose(0, 2, 1, 3).reshape(B * H, Sq, dh), 128, 2)
    kt = _pad_to(k.transpose(0, 2, 1, 3).reshape(B * K, Sk, dh), 128, 2)
    vt = _pad_to(v.transpose(0, 2, 1, 3).reshape(B * K, Sk, dh), 128, 2)
    Sq_p = ((Sq + block_q - 1) // block_q) * block_q
    Sk_p = ((Sk + block_k - 1) // block_k) * block_k
    qt = _pad_to(qt, block_q, 1)
    kt = _pad_to(kt, block_k, 1)
    vt = _pad_to(vt, block_k, 1)

    grid = (B * H, Sq_p // block_q, Sk_p // block_k)

    out = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, seq_k=Sk,
            q_offset=Sk - Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh_p), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, dh_p),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, block_k, dh_p),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh_p),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq_p, dh_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), F32),      # running max m
            pltpu.VMEM((block_q,), F32),      # running denom l
            pltpu.VMEM((block_q, dh_p), F32),  # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out[:, :Sq, :dh].reshape(B, H, Sq, dh).transpose(0, 2, 1, 3)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_pallas(q, k, v, causal: bool = True,
                           window: Optional[int] = None,
                           interpret: bool = False,
                           scale: Optional[float] = None):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=interpret, scale=scale)


def _fa_fwd(q, k, v, causal, window, interpret, scale):
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              interpret=interpret, scale=scale)
    return out, (q, k, v)


def _fa_bwd(causal, window, interpret, scale, res, g):
    q, k, v = res
    # Exact recompute backward via the jnp oracle (block-sparse Pallas
    # backward is future work; this keeps numerics bit-comparable).
    def f(q, k, v):
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 scale=scale)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention_pallas.defvjp(_fa_fwd, _fa_bwd)
