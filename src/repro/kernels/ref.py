"""Pure-jnp oracles for every Pallas kernel (the correctness references the
per-kernel test sweeps assert against, and the lowering used on non-TPU
backends / in the dry-run, where cost analysis must reflect real XLA HLO)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
NEG_INF = -1e30


# ------------------------------------------------------- flash attention
def mha_reference(q, k, v, causal: bool = True,
                  window: Optional[int] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """Exact softmax attention.  q: (B,Sq,H,dh); k/v: (B,Sk,K,dh), GQA."""
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(dh)
    qg = q.reshape(B, Sq, K, G, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(F32),
                   k.astype(F32)) * scale
    q_pos = jnp.arange(Sq)[:, None] + (k.shape[1] - Sq)
    k_pos = jnp.arange(k.shape[1])[None, :]
    if causal:
        mask = k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(F32))
    return o.reshape(B, Sq, H, dh).astype(q.dtype)


# ------------------------------------------------------- paged attention
def paged_attention_reference(q, k_pool, v_pool, page_table, lengths,
                              window: Optional[int] = None,
                              scale: Optional[float] = None) -> jax.Array:
    """Decode-time attention over paged KV.

    q: (B, H, dh) — one new token per sequence.
    k_pool/v_pool: (N_pages, P, K, dh) — one layer's HBM page pool (or
    flat, (N_pages, P, K*dh)).
    page_table: (B, MP) int32 — pool slot per logical page, -1 = unused.
    lengths: (B,) int32 — tokens so far (including the new one).
    ``scale`` defaults to 1/sqrt(dh).
    """
    B, H, dh = q.shape
    if k_pool.ndim == 3:
        k_pool = k_pool.reshape(k_pool.shape[:2] + (-1, dh))
        v_pool = v_pool.reshape(v_pool.shape[:2] + (-1, dh))
    N, P, K, _ = k_pool.shape
    MP = page_table.shape[1]
    G = H // K
    if scale is None:
        scale = 1.0 / np.sqrt(dh)

    safe = jnp.maximum(page_table, 0)
    k = k_pool[safe]            # (B, MP, P, K, dh)
    v = v_pool[safe]
    k = k.reshape(B, MP * P, K, dh)
    v = v.reshape(B, MP * P, K, dh)
    pos = jnp.arange(MP * P)[None, :]
    valid = (pos < lengths[:, None]) & jnp.repeat(
        page_table >= 0, P, axis=1)
    if window is not None:
        valid &= (lengths[:, None] - 1 - pos) < window

    qg = q.reshape(B, K, G, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(F32),
                   k.astype(F32)) * scale
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(F32))
    return o.reshape(B, H, dh).astype(q.dtype)


def paged_prefill_reference(q, k_pool, v_pool, page_table, lengths,
                            window: Optional[int] = None,
                            scale: Optional[float] = None) -> jax.Array:
    """One-shot prompt attention over paged KV — masked-einsum oracle.

    The S prompt tokens of ONE sequence are presented as S independent
    query rows over the same page table; row t's causal visibility is
    expressed through ``lengths[t]`` (= t+1 for real tokens, 0 for padded
    rows).  Delegates to the decode oracle with the table broadcast across
    rows, so a one-shot prefill computes bitwise the same function as
    stepping the tokens through decode one at a time — the property the
    serving engine's chunked-prefill equality tests pin down.

    q: (S, H, dh); k_pool/v_pool: (N, P, K, dh); page_table: (MP,) int32
    (-1 = unused); lengths: (S,) int32.  Returns (S, H, dh).
    """
    S = q.shape[0]
    table = jnp.broadcast_to(page_table[None, :], (S, page_table.shape[0]))
    return paged_attention_reference(q, k_pool, v_pool, table, lengths,
                                     window=window, scale=scale)


def paged_mla_decode_reference(q, pool, page_table, lengths, scale: float,
                               dv: int) -> jax.Array:
    """Absorbed multi-head latent attention over paged latents — jnp
    oracle.  q: (B, H, L) absorbed queries; pool: (N, P, L) one
    layer's latent pages; page_table: (B, MP) int32 (-1 = unused);
    lengths: (B,).  Scores are q.latent * ``scale``, values the latent's
    first ``dv`` lanes.  Returns (B, H, dv)."""
    B, H, L = q.shape
    P = pool.shape[1]
    MP = page_table.shape[1]
    lat = pool[jnp.maximum(page_table, 0)]
    lat = lat.reshape(B, MP * P, L).astype(F32)
    pos = jnp.arange(MP * P)[None, :]
    valid = (pos < lengths[:, None]) & jnp.repeat(page_table >= 0, P,
                                                   axis=1)
    s = jnp.einsum("bhl,bsl->bhs", q.astype(F32), lat) * scale
    s = jnp.where(valid[:, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bsv->bhv", w, lat[..., :dv])
    return o.astype(q.dtype)


# ---------------------------------------------------- grouped-expert GEMM
def moe_grouped_ffn_reference(x, w_gate, w_up, w_down, group_sizes,
                              group_experts=None):
    """Grouped-expert SwiGLU over sorted ragged segments — jnp oracle.

    x: (T, d) tokens sorted by group id (contiguous ragged segments);
    w_gate/w_up: (E, d, f); w_down: (E, f, d);
    group_sizes: (G,) int32 summing to T (empty groups allowed);
    group_experts: (G,) int32 mapping each group to its expert weights
    (None means G == E, the classic per-expert layout).

    Every expert's FFN is applied densely to all T rows, and the final
    einsum against the row->expert one-hot performs the segment-select (a
    segment_sum over the expert axis).  O(E) times the flops of the ragged
    kernel — it's the correctness oracle and the non-TPU lowering, where
    smoke-scale shapes make the overhead irrelevant.
    """
    T, d = x.shape
    E = w_gate.shape[0]
    G = group_sizes.shape[0]
    seg = jnp.repeat(jnp.arange(G), group_sizes, total_repeat_length=T)
    if group_experts is not None:
        seg = group_experts.astype(jnp.int32)[seg]
    xf = x.astype(F32)
    g = jnp.einsum("td,edf->etf", xf, w_gate.astype(F32))
    u = jnp.einsum("td,edf->etf", xf, w_up.astype(F32))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("etf,efd->etd", h, w_down.astype(F32))       # (E, T, d)
    sel = jax.nn.one_hot(seg, E, dtype=F32)                     # (T, E)
    out = jnp.einsum("etd,te->td", y, sel)
    return out.astype(x.dtype)


# ------------------------------------------------------------ sampling
def sample_tokens_reference(logits, seeds, positions, temperature, top_k,
                            top_p) -> np.ndarray:
    """Numpy oracle for ``kernels.sampling.sample_tokens``.

    Reimplements the sampling math — temperature scaling, top-k rank
    filter, top-p nucleus filter over the descending distribution,
    Gumbel-max selection, greedy short-circuit at ``temperature <= 0`` —
    independently in numpy, row by row.  Only the raw Gumbel bits are
    shared (``kernels.sampling.gumbel_noise``): they are the PRNG's replay
    contract, not sampling logic, and sharing them is what lets the sweep
    tests demand *exact* token equality rather than a distribution test.
    """
    from .sampling import gumbel_noise

    logits = np.asarray(logits)
    B, V = logits.shape
    seeds = np.asarray(seeds)
    positions = np.asarray(positions)
    temperature = np.asarray(temperature, np.float32)
    top_k = np.asarray(top_k)
    top_p = np.asarray(top_p, np.float32)
    out = np.zeros((B,), np.int32)
    for i in range(B):
        row = logits[i].astype(np.float32)
        if temperature[i] <= 0.0:
            out[i] = int(np.argmax(row))
            continue
        scaled = row / max(float(temperature[i]), 1e-6)
        order = np.argsort(-scaled, kind="stable")
        ranked = scaled[order]
        keep = np.ones((V,), bool)
        if 0 < top_k[i] < V:
            keep[int(top_k[i]):] = False
        shifted = (ranked - ranked.max()).astype(np.float32)
        probs = np.exp(shifted) / np.exp(shifted).sum(dtype=np.float32)
        cum = np.cumsum(probs, dtype=np.float32)
        keep &= (cum - probs) < float(top_p[i])     # rank 0 always kept
        masked = np.where(keep, ranked, NEG_INF)
        noise = np.asarray(
            gumbel_noise(int(seeds[i]), int(positions[i]), V))
        out[i] = int(order[np.argmax(masked + noise[order])])
    return out


# ------------------------------------------------------------- SSD scan
def ssd_reference(x, dt, A, Bm, Cm) -> jax.Array:
    """Naive O(S^2) SSD (Mamba2) reference.

    x: (B,S,H,P); dt: (B,S,H) f32; A: (H,) f32 negative; Bm/Cm: (B,S,N).
    y[t] = sum_{j<=t} C_t . B_j * exp(sum_{j<i<=t} dt_i A) * dt_j x_j.
    """
    Bsz, S, H, P = x.shape
    a = dt * A                                  # (B,S,H)
    a_cum = jnp.cumsum(a, axis=1)
    diff = a_cum[:, :, None, :] - a_cum[:, None, :, :]   # (B,S,S,H)
    causal = jnp.tril(jnp.ones((S, S), bool))
    diff = jnp.where(causal[None, :, :, None], diff, -1e30)
    L = jnp.exp(diff)
    scores = jnp.einsum("bin,bjn->bij", Cm.astype(F32), Bm.astype(F32))
    xdt = x.astype(F32) * dt[..., None]
    y = jnp.einsum("bij,bijh,bjhp->bihp", scores, L, xdt)
    return y


def ssm_decode_reference(pool, layer, slots, x, dt, A, D, Bm, Cm):
    """Mamba-2 decode step over a state pool — jnp oracle of
    ``kernels/ssm_decode.py``.  pool (Lm, slots, N, H*P) f32; x (R, H*P);
    dt (R, H) f32; A, D (H,); Bm, Cm (R, G, N).  Returns (y (R, H*P) f32,
    the pool with layer ``layer``'s rows ``slots`` advanced one token)."""
    _, _, N, HP = pool.shape
    R, H = dt.shape
    P = HP // H
    rep = H // Bm.shape[1]
    Bh = jnp.repeat(Bm.astype(F32), rep, axis=1)             # (R, H, N)
    Ch = jnp.repeat(Cm.astype(F32), rep, axis=1)
    xf = x.astype(F32).reshape(R, H, P)
    state = pool[layer, slots].reshape(R, N, H, P)
    new = (jnp.exp(dt * A)[:, None, :, None] * state
           + jnp.einsum("rhn,rhp->rnhp", Bh, xf * dt[..., None]))
    y = jnp.einsum("rhn,rnhp->rhp", Ch, new) + xf * D[None, :, None]
    pool = pool.at[layer, slots].set(new.reshape(R, N, HP))
    return y.reshape(R, HP), pool
