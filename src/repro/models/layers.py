"""Core transformer layers: RMSNorm, rotary embeddings, GQA attention
(training/prefill via chunked online-softmax, decode against a KV cache,
optional sliding window, optional cross-attention), SwiGLU MLP, embeddings.

All functions are pure; parameters are nested dicts produced from the
``ArrayDef`` declarations.  Activation sharding is expressed with logical
axes via ``constrain`` so the same code partitions on any mesh.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.sharding import constrain
from .common import ArrayDef

F32 = jnp.float32
NEG_INF = -1e30


# ------------------------------------------------------------------ rmsnorm
def rmsnorm_defs(d: int):
    return {"scale": ArrayDef((d,), ("embed",), init="ones")}


def rmsnorm(p, x, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    y = x.astype(F32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(F32)).astype(x.dtype)


# -------------------------------------------------------------------- rope
def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0,
         freqs: Optional[np.ndarray] = None, mscale: float = 1.0):
    """x: (..., S, H, dh); positions: (..., S).  Half-split pairs (lane i
    with lane i + dh/2).  ``freqs`` (dh/2,) overrides the plain inverse
    frequencies (YaRN, ``yarn_freqs``); ``mscale`` scales cos and sin."""
    dh = x.shape[-1]
    half = dh // 2
    if freqs is None:
        freqs = (theta ** (-np.arange(0, half, dtype=np.float32) / half))
    angles = positions[..., :, None].astype(F32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention
@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: Optional[int] = None      # sliding-window size (None = full)
    causal: bool = True
    use_rope: bool = True             # False: NoPE
    scale: Optional[float] = None     # softmax scale (None: 1/sqrt(dh))


def attention_defs(cfg: AttnConfig):
    d, H, K, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return {
        "wq": ArrayDef((d, H, dh), ("embed", "heads", None)),
        "wk": ArrayDef((d, K, dh), ("embed", "kv_heads", None)),
        "wv": ArrayDef((d, K, dh), ("embed", "kv_heads", None)),
        "wo": ArrayDef((H, dh, d), ("heads", None, "embed")),
    }


def _qkv(p, x, cfg: AttnConfig, positions, kv_x=None, use_rope=True):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    src = x if kv_x is None else kv_x
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "kv_seq", "kv_heads", None))
    v = constrain(v, ("batch", "kv_seq", "kv_heads", None))
    if use_rope and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mask(q_pos, k_pos, cfg: AttnConfig, cross: bool):
    """(…, Sq, Sk) boolean mask of *allowed* attention."""
    if cross:
        return None
    allowed = k_pos[..., None, :] <= q_pos[..., :, None]
    if cfg.window is not None:
        allowed &= (q_pos[..., :, None] - k_pos[..., None, :]) < cfg.window
    return allowed


def _attend(q, k, v, mask, cfg: AttnConfig, q_chunk: int = 1024,
            scale: Optional[float] = None):
    """Grouped-query attention with chunked-q exact softmax.

    q/k: (B, S, H|K, dh); v: (B, Sk, K, dv).  Memory per chunk is
    O(B*H*q_chunk*Sk), never O(Sq*Sk).  ``scale`` defaults to
    1/sqrt(dh).
    """
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    dv = v.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(dh)
    qg = q.reshape(B, Sq, K, G, dh)

    n_chunks = max(1, Sq // q_chunk) if Sq % q_chunk == 0 else 1
    qc = Sq // n_chunks

    @jax.checkpoint
    def one_chunk(args):
        # Rematerialized: the (qc, Sk) score block is never stored for the
        # backward pass — flash-style memory behaviour at XLA level.  The
        # Pallas kernel (kernels/flash_attention.py) replaces this on TPU.
        q_blk, mask_blk = args  # (B, qc, K, G, dh), (qc, Sk) | None
        s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k,
                       preferred_element_type=F32) * scale
        if mask_blk is not None:
            s = jnp.where(mask_blk[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(F32))
        return o.astype(v.dtype)

    if n_chunks == 1:
        out = one_chunk((qg, mask))
    else:
        qs = qg.reshape(B, n_chunks, qc, K, G, dh).transpose(1, 0, 2, 3, 4, 5)
        ms = (
            mask.reshape(n_chunks, qc, -1)
            if mask is not None
            else jnp.zeros((n_chunks, 0, 0), bool)
        )
        outs = jax.lax.map(
            lambda a: one_chunk((a[0], a[1] if mask is not None else None)),
            (qs, ms),
        )
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, K, G, dv)
    return out.reshape(B, Sq, H, dv)


def attention(p, x, cfg: AttnConfig, positions, kv_x=None,
              kv_positions=None, q_chunk: int = 1024):
    """Full attention for training/prefill (self or cross)."""
    cross = kv_x is not None
    q, k, v = _qkv(p, x, cfg, positions, kv_x=kv_x,
                   use_rope=cfg.use_rope and not cross)
    k_pos = kv_positions if kv_positions is not None else positions
    mask = _mask(positions[0], k_pos[0], cfg, cross)  # same for all batch rows
    out = _attend(q, k, v, mask, cfg, q_chunk=q_chunk, scale=cfg.scale)
    out = constrain(out, ("batch", "seq", "heads", None))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return constrain(y, ("batch", "seq", "embed"))


# ------------------------------------------------------------ decode (1 tok)
def attention_decode(p, x, cache_k, cache_v, pos, cfg: AttnConfig):
    """One-token decode: update the cache at ``pos`` and attend over it.

    x: (B, 1, d); cache_k/v: (B, S_max, K, dh); pos: scalar int32.
    Returns (y, new_cache_k, new_cache_v).
    """
    B, S_max, K, dh = cache_k.shape
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k1 = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v1 = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k1 = rope(k1, positions, cfg.rope_theta)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k1.astype(cache_k.dtype),
                                           (0, pos, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v1.astype(cache_v.dtype),
                                           (0, pos, 0, 0))
    cache_k = constrain(cache_k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    cache_v = constrain(cache_v, ("batch", "kv_seq", "kv_heads", "head_dim"))

    H = cfg.n_heads
    G = H // K
    # Match the cache layout (kv_heads replicated when K < model axis,
    # head_dim sharded): without this, SPMD re-shards the whole cache per
    # step ("involuntary full rematerialization" — a per-token all-gather of
    # the KV cache).  Contraction over the sharded head_dim instead costs a
    # small psum of the (B,K,G,S) scores.
    qg = constrain(q.reshape(B, K, G, dh),
                   ("batch", "kv_heads", None, "qdh"))
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(F32),
                   cache_k.astype(F32))
    s *= 1.0 / np.sqrt(dh) if cfg.scale is None else cfg.scale
    k_pos = jnp.arange(S_max)
    allowed = k_pos <= pos
    if cfg.window is not None:
        allowed &= (pos - k_pos) < cfg.window
    s = jnp.where(allowed[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, cache_v.astype(F32))
    o = o.astype(x.dtype)
    y = jnp.einsum("bhk,hkd->bd", o.reshape(B, H, dh), p["wo"])[:, None]
    return constrain(y, ("batch", "seq", "embed")), cache_k, cache_v


def attention_decode_ring(p, x, cache_k, cache_v, pos, cfg: AttnConfig):
    """One-token decode against a *ring-buffer* KV cache of size window
    (sliding-window attention never needs older entries — §Perf climb #3).

    cache_k/v: (B, W, K, dh) where W == cfg.window; slot = pos % W.  Keys are
    stored rope'd at their absolute positions, so ring rotation is free.
    """
    B, W, K, dh = cache_k.shape
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k1 = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v1 = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = rope(q, positions, cfg.rope_theta)
    k1 = rope(k1, positions, cfg.rope_theta)
    slot = jax.lax.rem(pos, W)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k1.astype(cache_k.dtype),
                                           (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v1.astype(cache_v.dtype),
                                           (0, slot, 0, 0))
    cache_k = constrain(cache_k, ("batch", "kv_seq", "kv_heads", "head_dim"))
    cache_v = constrain(cache_v, ("batch", "kv_seq", "kv_heads", "head_dim"))

    H = cfg.n_heads
    G = H // K
    qg = constrain(q.reshape(B, K, G, dh),
                   ("batch", "kv_heads", None, "qdh"))
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(F32), cache_k.astype(F32))
    s *= 1.0 / np.sqrt(dh)
    # Ring validity: during warmup (pos < W-1) only slots <= pos hold data;
    # afterwards every slot is within the window by construction.
    valid = jnp.arange(W) <= pos
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, cache_v.astype(F32))
    o = o.astype(x.dtype)
    y = jnp.einsum("bhk,hkd->bd", o.reshape(B, H, dh), p["wo"])[:, None]
    return constrain(y, ("batch", "seq", "embed")), cache_k, cache_v


def cross_attention_decode(p, x, mem_k, mem_v, cfg: AttnConfig):
    """Decode-time cross attention over precomputed encoder memory."""
    B = x.shape[0]
    H, K = cfg.n_heads, cfg.kv_heads
    dh = cfg.head_dim
    G = H // K
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"]).reshape(B, K, G, dh)
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(F32),
                   mem_k.astype(F32)) / np.sqrt(dh)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", w, mem_v.astype(F32)).astype(x.dtype)
    y = jnp.einsum("bhk,hkd->bd", o.reshape(B, H, dh), p["wo"])[:, None]
    return y


# ------------------------------------------- multi-head latent attention
def yarn_freqs(dim: int, theta: float, factor: float, original_max: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies of ``dim`` rope lanes (arXiv:2309.00071,
    as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` computes them):
    pairs that turn more than ``beta_fast`` times over the original
    context keep their frequency, those under ``beta_slow`` turns are
    divided by ``factor``, and a linear ramp blends the pairs between."""
    def turns_dim(rot):
        return (dim * math.log(original_max / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    expo = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (theta ** expo)
    inter = 1.0 / (factor * theta ** expo)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 Sec.
    2.1) without query compression.  Per token one latent of
    ``kv_lora_rank + qk_rope`` values is cached: the normed compressed KV
    ``c`` and the rotated shared rope key ``k_pe``.  Head h's key is
    [c W_uk[h], k_pe], its value c W_uv[h]."""
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head_dim: int
    rope_theta: float = 10000.0
    yarn: Optional[Any] = None        # models.config.Yarn

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope

    def rope_freqs(self) -> Optional[np.ndarray]:
        y = self.yarn
        if y is None:
            return None
        return yarn_freqs(self.qk_rope, self.rope_theta, y.factor,
                          y.original_max_position, y.beta_fast, y.beta_slow)

    def rope_mscale(self) -> float:
        y = self.yarn
        if y is None:
            return 1.0
        return (yarn_mscale(y.factor, y.mscale)
                / yarn_mscale(y.factor, y.mscale_all_dim))

    def softmax_scale(self) -> float:
        """(qk_nope + qk_rope)^-1/2, times YaRN's mscale squared."""
        scale = 1.0 / math.sqrt(self.qk_nope + self.qk_rope)
        y = self.yarn
        if y is not None and y.mscale_all_dim:
            m = yarn_mscale(y.factor, y.mscale_all_dim)
            scale *= m * m
        return scale


def mla_defs(cfg: MLAConfig):
    d, H, C = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope, cfg.qk_rope, cfg.v_head_dim
    # W_kv_b's two halves are ``wk`` and ``wv``, shaped (C, H, dh) so the
    # contraction axis leads; every std is 1/sqrt(fan-in).
    return {
        "wq": ArrayDef((d, H, dn + dr), ("embed", "heads", None),
                       scale=1.0 / np.sqrt(d)),
        "wkv_a": ArrayDef((d, C + dr), ("embed", None)),
        "kv_norm": {"scale": ArrayDef((C,), (None,), init="ones")},
        "wk": ArrayDef((C, H, dn), (None, "heads", None),
                       scale=1.0 / np.sqrt(C)),
        "wv": ArrayDef((C, H, dv), (None, "heads", None),
                       scale=1.0 / np.sqrt(C)),
        "wo": ArrayDef((H, dv, d), ("heads", None, "embed"),
                       scale=1.0 / np.sqrt(H * dv)),
    }


def mla_project(p, h, positions, cfg: MLAConfig):
    """h: (..., S, d); positions (..., S).  Returns the query's nope and
    rotated rope parts (..., S, H, qk_nope|qk_rope) and the token's cache
    latent (..., S, kv_lora_rank + qk_rope): [rmsnorm(c), rope(k_pe)]."""
    C = cfg.kv_lora_rank
    freqs, ms = cfg.rope_freqs(), cfg.rope_mscale()
    q = jnp.einsum("...sd,dhk->...shk", h, p["wq"])
    q_nope = q[..., :cfg.qk_nope]
    q_pe = rope(q[..., cfg.qk_nope:], positions, cfg.rope_theta, freqs, ms)
    kv = jnp.einsum("...sd,dc->...sc", h, p["wkv_a"])
    c = rmsnorm(p["kv_norm"], kv[..., :C])
    k_pe = rope(kv[..., None, C:], positions, cfg.rope_theta, freqs,
                ms)[..., 0, :]
    return q_nope, q_pe, jnp.concatenate([c, k_pe], axis=-1)


def mla_unabsorbed(p, q_nope, q_pe, lat, cfg: MLAConfig):
    """Per-head queries, keys and values of the plain form: q (.., Sq, H,
    qk_nope+qk_rope), k (.., Sk, H, qk_nope+qk_rope), v (.., Sk, H, dv)."""
    C = cfg.kv_lora_rank
    c, k_pe = lat[..., :C], lat[..., C:]
    k_nope = jnp.einsum("...c,chn->...hn", c, p["wk"])
    v = jnp.einsum("...c,chv->...hv", c, p["wv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[..., None, :],
                                  k_nope.shape[:-1] + k_pe.shape[-1:])],
        axis=-1)
    return jnp.concatenate([q_nope, q_pe], axis=-1), k, v


def mla_absorb(p, q_nope, q_pe):
    """The absorbed query (..., H, kv_lora_rank + qk_rope): q_nope W_uk^T
    beside q_pe, whose product with a cached latent is the plain score."""
    return jnp.concatenate(
        [jnp.einsum("...hn,chn->...hc", q_nope, p["wk"]), q_pe], axis=-1)


def mla_values(p, o_lat):
    """Attention output over latents (..., H, kv_lora_rank) to head
    values (..., H, dv): o_lat W_uv."""
    return jnp.einsum("...hc,chv->...hv", o_lat, p["wv"])


def mla_attention(p, x, cfg: MLAConfig, positions, q_chunk: int = 1024):
    """Causal MLA for training/prefill, in the plain (un-absorbed) form."""
    q_nope, q_pe, lat = mla_project(p, x, positions, cfg)
    q, k, v = mla_unabsorbed(p, q_nope, q_pe, lat, cfg)
    pos = positions[0]
    mask = pos[None, :] <= pos[:, None]
    out = _attend(q, k, v, mask, None, q_chunk=q_chunk,
                  scale=cfg.softmax_scale())
    out = constrain(out, ("batch", "seq", "heads", None))
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return constrain(y, ("batch", "seq", "embed"))


def mla_attention_decode(p, x, cache, pos, cfg: MLAConfig):
    """One-token decode against a dense latent cache, absorbed: update the
    cache at ``pos`` and attend over it.  x: (B, 1, d); cache: (B, S_max,
    kv_lora_rank + qk_rope).  Returns (y, new_cache)."""
    B, S_max, _ = cache.shape
    positions = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_pe, lat = mla_project(p, x, positions, cfg)
    cache = jax.lax.dynamic_update_slice(cache, lat.astype(cache.dtype),
                                         (0, pos, 0))
    q_lat = mla_absorb(p, q_nope[:, 0], q_pe[:, 0])          # (B, H, L)
    s = jnp.einsum("bhc,bsc->bhs", q_lat.astype(F32), cache.astype(F32))
    s *= cfg.softmax_scale()
    s = jnp.where((jnp.arange(S_max) <= pos)[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsc->bhc", w,
                       cache[..., :cfg.kv_lora_rank].astype(F32))
    o = mla_values(p, o_lat.astype(x.dtype))
    y = jnp.einsum("bhv,hvd->bd", o, p["wo"])[:, None]
    return constrain(y, ("batch", "seq", "embed")), cache


# --------------------------------------------------------------------- mlp
def mlp_defs(d: int, f: int):
    return {
        "w_gate": ArrayDef((d, f), ("embed", "mlp")),
        "w_up": ArrayDef((d, f), ("embed", "mlp")),
        "w_down": ArrayDef((f, d), ("mlp", "embed")),
    }


def mlp(p, x):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g.astype(F32)).astype(x.dtype) * u
    h = constrain(h, ("batch", "seq", "mlp"))
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return constrain(y, ("batch", "seq", "embed"))


# -------------------------------------------------------------- embeddings
def embed_defs(vocab: int, d: int):
    return {"tok": ArrayDef((vocab, d), ("vocab", "embed"), init="embed",
                            scale=0.02)}


def embed(p, tokens):
    out = jnp.take(p["tok"], tokens, axis=0)
    return constrain(out, ("batch", "seq", "embed"))


def lm_head_defs(d: int, vocab: int):
    return {"w": ArrayDef((d, vocab), ("embed", "vocab"))}


def lm_head(p, x):
    logits = jnp.einsum("bsd,dv->bsv", x, p["w"]).astype(F32)
    return constrain(logits, ("batch", "seq", "vocab"))


def cross_entropy(logits, labels, ignore: int = -1):
    """Mean CE over non-ignored positions.  logits f32 (B,S,V)."""
    mask = (labels != ignore)
    safe = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


def chunked_lm_loss(head_p, x, labels, ignore: int = -1,
                    chunk: int = 512):
    """lm_head + CE without materializing (B,S,V) f32 logits: the sequence
    is processed in rematerialized chunks (critical for 256k vocabularies).
    Returns (sum_nll, count) reduced over the whole batch."""
    B, S, d = x.shape
    n = max(1, S // chunk) if S % chunk == 0 else 1
    xs = x.reshape(B, n, S // n, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, n, S // n).transpose(1, 0, 2)

    @jax.checkpoint
    def one(args):
        xc, lc = args
        logits = lm_head(head_p, xc)
        mask = (lc != ignore)
        safe = jnp.where(mask, lc, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return ((logz - gold) * mask).sum(), mask.sum()

    if n == 1:
        nll, cnt = one((xs[0], ls[0]))
    else:
        nlls, cnts = jax.lax.map(one, (xs, ls))
        nll, cnt = nlls.sum(), cnts.sum()
    return nll / jnp.maximum(cnt, 1)
