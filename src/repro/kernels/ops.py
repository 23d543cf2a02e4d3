"""jit'd kernel wrappers with backend dispatch.

Modes:
  * "pallas"    — pl.pallas_call TPU kernels (kernels/<name>.py),
  * "interpret" — same kernels, Pallas interpret mode (CPU validation),
  * "reference" — pure-jnp oracles (kernels/ref.py).

Default: pallas on TPU, reference elsewhere — so dry-run cost analysis on
the CPU backend reflects honest XLA HLO, while TPU runs get the tiled
kernels.  Override per-call or globally via ``set_mode``.
"""

from __future__ import annotations

from typing import Optional

import jax

from . import ref

_MODE: Optional[str] = None  # None = auto


def set_mode(mode: Optional[str]):
    global _MODE
    assert mode in (None, "pallas", "interpret", "reference")
    _MODE = mode


def current_mode() -> str:
    if _MODE is not None:
        return _MODE
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "reference"


def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None):
    """q: (B,Sq,H,dh); k/v: (B,Sk,K,dh).  ``scale`` defaults to
    1/sqrt(dh)."""
    mode = current_mode()
    if mode == "reference":
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 scale=scale)
    from .flash_attention import flash_attention_pallas

    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=(mode == "interpret"),
                                  scale=scale)


def paged_attention(q, k_pool, v_pool, page_table, lengths,
                    window: Optional[int] = None,
                    scale: Optional[float] = None):
    """Decode attention over paged K/V pools, (N,P,K,dh) or flat
    (N,P,K*dh); ``scale`` defaults to 1/sqrt(dh)."""
    mode = current_mode()
    if mode == "reference":
        return ref.paged_attention_reference(q, k_pool, v_pool, page_table,
                                             lengths, window=window,
                                             scale=scale)
    from .paged_attention import paged_attention_pallas

    return paged_attention_pallas(q, k_pool, v_pool, page_table, lengths,
                                  window=window,
                                  interpret=(mode == "interpret"),
                                  scale=scale)


def paged_prefill(q, k_pool, v_pool, page_table, lengths,
                  window: Optional[int] = None,
                  scale: Optional[float] = None):
    """One-shot prompt attention over paged KV: the S prompt tokens of one
    sequence attend as S query rows over a shared page table, with row t's
    causal visibility carried by ``lengths[t]`` (0 disables a padded row).
    Both lowerings reuse the decode paged-attention math, so a whole-prompt
    prefill is bitwise-equal to stepping its tokens through decode.

    q: (S,H,dh); k_pool/v_pool: (N,P,K,dh); page_table: (MP,) int32;
    lengths: (S,) int32.  Returns (S,H,dh)."""
    mode = current_mode()
    if mode == "reference":
        return ref.paged_prefill_reference(q, k_pool, v_pool, page_table,
                                           lengths, window=window,
                                           scale=scale)
    from .paged_attention import paged_prefill_pallas

    return paged_prefill_pallas(q, k_pool, v_pool, page_table, lengths,
                                window=window,
                                interpret=(mode == "interpret"),
                                scale=scale)


def paged_mla_decode(q, pool, page_table, lengths, scale: float, dv: int):
    """Absorbed multi-head latent attention decode over ONE paged latent
    pool (keys and values share it).  q: (B,H,L); pool: (N,P,L);
    page_table: (B,MP) int32; lengths: (B,).  Returns (B,H,dv)."""
    mode = current_mode()
    if mode == "reference":
        return ref.paged_mla_decode_reference(q, pool, page_table, lengths,
                                              scale=scale, dv=dv)
    from .paged_attention import paged_mla_decode_pallas

    return paged_mla_decode_pallas(q, pool, page_table, lengths,
                                   scale=scale, dv=dv,
                                   interpret=(mode == "interpret"))


def moe_grouped_ffn(x, w_gate, w_up, w_down, group_sizes,
                    group_experts=None):
    """Grouped-expert SwiGLU over sorted ragged segments (dropless MoE
    dispatch).  x: (T, d) argsorted by group; group_sizes: (G,) int32;
    group_experts: optional (G,) int32 group->expert weight map (None means
    G == E and groups are experts)."""
    mode = current_mode()
    if mode == "reference":
        return ref.moe_grouped_ffn_reference(x, w_gate, w_up, w_down,
                                             group_sizes, group_experts)
    from .moe_gemm import moe_grouped_ffn_pallas

    return moe_grouped_ffn_pallas(x, w_gate, w_up, w_down, group_sizes,
                                  group_experts,
                                  interpret=(mode == "interpret"))


def sample_tokens(logits, seeds, positions, temperature, top_k, top_p):
    """Batched in-dispatch token sampling (decode epilogue): temperature /
    top-k / top-p filtering + Gumbel-max over (B, vocab) logits rows, with
    per-row keys ``fold_in(PRNGKey(seed), position)`` so preempted or
    re-prefilled requests replay identical streams.  ``temperature <= 0``
    rows are bitwise-equal to ``argmax(logits)``.

    Single lowering on every backend: the epilogue is a sort + cumsum +
    argmax that XLA fuses into the logits consumer, so there is no separate
    Pallas kernel to dispatch to — the numpy oracle for the test sweeps
    lives in ``ref.sample_tokens_reference``.
    """
    from .sampling import sample_tokens as _sample_tokens

    return _sample_tokens(logits, seeds, positions, temperature, top_k,
                          top_p)


def ssm_decode(pool, layer, slots, x, dt, A, D, Bm, Cm):
    """Mamba-2 decode step of R rows over layer ``layer`` of a recurrent
    state pool (Lm, slots, N, H*P) f32, each row's state at
    ``slots[r]``, updated in place.  x (R, H*P); dt (R, H) f32 after
    softplus; A, D (H,); Bm, Cm (R, G, N).  Returns (y (R, H*P) f32 with
    the D term, the pool)."""
    mode = current_mode()
    if mode == "reference":
        return ref.ssm_decode_reference(pool, layer, slots, x, dt, A, D,
                                        Bm, Cm)
    from .ssm_decode import ssm_decode_pallas

    return ssm_decode_pallas(pool, layer, slots, x, dt, A, D, Bm, Cm,
                             interpret=(mode == "interpret"))


def ssm_state_read(pool, layer, slot):
    """Row ``slot`` of layer ``layer`` of a state pool, (N, H*P)."""
    mode = current_mode()
    if mode == "reference":
        return pool[layer, slot]
    from .ssm_decode import ssm_state_read_pallas

    return ssm_state_read_pallas(pool, layer, slot,
                                 interpret=(mode == "interpret"))


def ssm_state_write(pool, layer, slot, state):
    """The pool with row ``slot`` of layer ``layer`` set to ``state``."""
    mode = current_mode()
    if mode == "reference":
        return pool.at[layer, slot].set(state.astype(pool.dtype))
    from .ssm_decode import ssm_state_write_pallas

    return ssm_state_write_pallas(pool, layer, slot, state,
                                  interpret=(mode == "interpret"))


def ssd_scan(x, dt, A, Bm, Cm):
    """Intra-chunk SSD block (one chunk).  Cross-chunk recurrence stays in
    models/ssm.py regardless of backend."""
    mode = current_mode()
    if mode == "reference":
        return ref.ssd_reference(x, dt, A, Bm, Cm)
    from .ssd_scan import ssd_scan_pallas

    return ssd_scan_pallas(x, dt, A, Bm, Cm,
                           interpret=(mode == "interpret"))
