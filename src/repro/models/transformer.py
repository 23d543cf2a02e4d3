"""Architecture assembly: builds every supported family from ModelConfig.

One ``Model`` object per architecture exposes:
  * ``param_defs()`` / ``init(key)``       — declarations / materialization
  * ``loss(params, batch)``                — training forward (+ CE loss)
  * ``prefill(params, batch, cache)``      — prompt ingestion, fills cache
  * ``decode(params, cache, tokens, pos)`` — one-token serve step
  * ``cache_defs(batch, seq)``             — KV/state cache declarations
  * ``input_specs(shape)``                 — ShapeDtypeStructs for AOT lowering

Layer stacks run under ``jax.lax.scan`` (stacked params) with optional remat;
heterogeneous stacks (zamba2 hybrid, xLSTM) are segmented: uniform segments
scan, the interleaved special blocks (shared attention / sLSTM) unroll.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.sharding import constrain
from .common import ArrayDef, abstract_params, init_params, stack
from .config import ModelConfig, ShapeConfig
from .layers import (
    AttnConfig,
    MLAConfig,
    chunked_lm_loss,
    attention,
    attention_decode,
    attention_decode_ring,
    attention_defs,
    cross_attention_decode,
    cross_entropy,
    embed,
    embed_defs,
    lm_head,
    lm_head_defs,
    mla_attention,
    mla_attention_decode,
    mla_defs,
    mla_project,
    mlp,
    mlp_defs,
    rmsnorm,
    rmsnorm_defs,
    rope,
)
from .moe import MoEConfig, moe, moe_decode, moe_defs
from .ssm import SSMConfig, ssm_decode, ssm_defs, ssm_forward
from .xlstm import (
    XLSTMConfig,
    mlstm_decode,
    mlstm_defs,
    mlstm_parallel,
    slstm_decode,
    slstm_defs,
    slstm_forward,
)

PyTree = Any
F32 = jnp.float32


def build_model(cfg: ModelConfig) -> "Model":
    cfg.validate()
    return Model(cfg)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.attn_cfg = AttnConfig(
            d_model=cfg.d_model,
            n_heads=cfg.n_heads,
            kv_heads=cfg.kv_heads,
            head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta,
            window=cfg.window,
            use_rope=cfg.use_rope,
            scale=cfg.attention_multiplier,
        )
        self.mla_cfg = MLAConfig(
            d_model=cfg.d_model, n_heads=cfg.n_heads,
            kv_lora_rank=cfg.kv_lora_rank, qk_nope=cfg.qk_nope_head_dim,
            qk_rope=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
            rope_theta=cfg.rope_theta, yarn=cfg.yarn) if cfg.mla else None
        if cfg.family == "moe":
            self.moe_cfg = MoEConfig(
                d_model=cfg.d_model, d_ff=cfg.d_ff, n_experts=cfg.n_experts,
                top_k=cfg.top_k, capacity_factor=cfg.capacity_factor,
                dispatch=cfg.moe_dispatch,
                parallelism=cfg.moe_parallelism,
                ep_axis_size=cfg.moe_ep_axis_size,
                norm_topk_prob=cfg.norm_topk_prob,
                n_local=cfg.n_local_experts,
            )
        if cfg.family == "hybrid" or cfg.mixed:
            self.ssm_cfg = SSMConfig(
                d_model=cfg.d_model, d_inner=cfg.d_inner,
                head_dim=cfg.ssm_head_dim, state_dim=cfg.ssm_state,
                n_groups=cfg.ssm_groups, conv_width=cfg.ssm_conv,
                conv_bias=cfg.ssm_conv_bias, chunk=cfg.ssm_chunk,
                eps=cfg.norm_eps,
            )
        if cfg.family == "xlstm":
            self.xl_cfg = XLSTMConfig(d_model=cfg.d_model, n_heads=cfg.n_heads)

    # ================================================================ defs
    def _attn_defs(self):
        if self.mla_cfg is not None:
            return mla_defs(self.mla_cfg)
        return attention_defs(self.attn_cfg)

    def _dense_layer_defs(self):
        cfg = self.cfg
        d = {"ln1": rmsnorm_defs(cfg.d_model),
             "attn": self._attn_defs(),
             "ln2": rmsnorm_defs(cfg.d_model)}
        if cfg.family == "moe":
            d["moe"] = moe_defs(self.moe_cfg)
            if cfg.n_shared_experts:
                d["shared"] = mlp_defs(cfg.d_model,
                                       cfg.n_shared_experts * cfg.d_ff)
        else:
            d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff)
        return d

    def _lead_layer_defs(self):
        """A leading dense layer of a MoE model: SwiGLU of dense_d_ff."""
        cfg = self.cfg
        return {"ln1": rmsnorm_defs(cfg.d_model),
                "attn": self._attn_defs(),
                "ln2": rmsnorm_defs(cfg.d_model),
                "mlp": mlp_defs(cfg.d_model, cfg.dense_d_ff)}

    def _shared_attn_defs(self):
        return {"ln1": rmsnorm_defs(self.cfg.d_model),
                "attn": attention_defs(self.attn_cfg),
                "ln2": rmsnorm_defs(self.cfg.d_model),
                "mlp": mlp_defs(self.cfg.d_model, self.cfg.d_ff)}

    def param_defs(self) -> PyTree:
        cfg = self.cfg
        out: Dict[str, PyTree] = {"final_ln": rmsnorm_defs(cfg.d_model)}
        if cfg.tie_embeddings:
            # One (vocab, d) table, the embedding and (transposed) the
            # output head, under its own name (``embed_table``).
            out["tied_embed"] = embed_defs(cfg.vocab, cfg.d_model)
        else:
            out["embed"] = embed_defs(cfg.vocab, cfg.d_model)
            out["head"] = lm_head_defs(cfg.d_model, cfg.vocab)
        if cfg.mixed:
            out["layers"] = self._mixed_layer_defs()
        elif cfg.family in ("dense", "moe", "vlm"):
            nd = cfg.first_dense_layers
            if nd:
                out["dense_layers"] = stack(self._lead_layer_defs(), nd)
            out["layers"] = stack(self._dense_layer_defs(),
                                  cfg.n_layers - nd)
            if cfg.family == "vlm":
                out["vision_adapter"] = {
                    "w": ArrayDef((cfg.d_model, cfg.d_model),
                                  ("embed", "embed"))}
        elif cfg.family == "hybrid":
            out["ssm_layers"] = stack(
                {"ln": rmsnorm_defs(cfg.d_model),
                 "ssm": ssm_defs(self.ssm_cfg)}, cfg.n_layers)
            out["shared_attn"] = self._shared_attn_defs()
        elif cfg.family == "xlstm":
            n_m, n_s = self._xlstm_counts()
            out["mlstm_layers"] = stack(
                {"ln": rmsnorm_defs(cfg.d_model),
                 "mlstm": mlstm_defs(self.xl_cfg)}, n_m)
            if n_s:
                out["slstm_layers"] = stack(
                    {"ln": rmsnorm_defs(cfg.d_model),
                     "slstm": slstm_defs(self.xl_cfg)}, n_s)
        elif cfg.family == "encdec":
            out["audio_adapter"] = {
                "w": ArrayDef((cfg.d_model, cfg.d_model), ("embed", "embed"))}
            out["enc_layers"] = stack(
                {"ln1": rmsnorm_defs(cfg.d_model),
                 "attn": attention_defs(self.attn_cfg),
                 "ln2": rmsnorm_defs(cfg.d_model),
                 "mlp": mlp_defs(cfg.d_model, cfg.d_ff)}, cfg.enc_layers)
            out["dec_layers"] = stack(
                {"ln1": rmsnorm_defs(cfg.d_model),
                 "attn": attention_defs(self.attn_cfg),
                 "lnx": rmsnorm_defs(cfg.d_model),
                 "xattn": attention_defs(self.attn_cfg),
                 "ln2": rmsnorm_defs(cfg.d_model),
                 "mlp": mlp_defs(cfg.d_model, cfg.d_ff)}, cfg.n_layers)
        else:
            raise ValueError(f"unknown family {cfg.family}")
        return out

    def _mixed_layer_defs(self):
        """A stack whose layers differ by mixer: per-layer norms, experts
        and shared expert stacked over every layer, attention and Mamba-2
        weights each over the layers of their kind, in depth order."""
        cfg = self.cfg
        L, kinds = cfg.n_layers, cfg.mixers
        out = {"ln1": stack(rmsnorm_defs(cfg.d_model), L),
               "ln2": stack(rmsnorm_defs(cfg.d_model), L),
               "moe": stack(moe_defs(self.moe_cfg), L),
               "attn": stack(attention_defs(self.attn_cfg),
                             kinds.count("attention")),
               "mamba": stack(ssm_defs(self.ssm_cfg), kinds.count("mamba"))}
        if cfg.n_shared_experts:
            out["shared"] = stack(
                mlp_defs(cfg.d_model, cfg.n_shared_experts * cfg.d_ff), L)
        return out

    def _xlstm_counts(self) -> Tuple[int, int]:
        L, k = self.cfg.n_layers, self.cfg.slstm_every
        n_s = L // k if k else 0
        return L - n_s, n_s

    def init(self, key) -> PyTree:
        return init_params(self.param_defs(), key)

    def abstract(self) -> PyTree:
        return abstract_params(self.param_defs())

    # =========================================================== forward
    def _maybe_remat(self, fn):
        return jax.checkpoint(fn) if self.cfg.remat else fn

    def _self_attention(self, p, h, positions):
        if self.mla_cfg is not None:
            return mla_attention(p, h, self.mla_cfg, positions)
        return attention(p, h, self.attn_cfg, positions)

    def _ffn(self, lp, h2, decode: bool = False):
        """A layer's FFN: the routed experts (plus the shared ones), or the
        dense MLP of a dense model or a MoE model's leading layer."""
        if "moe" in lp:
            d = (moe_decode if decode else moe)(lp["moe"], h2, self.moe_cfg)
            if "shared" in lp:
                d = d + mlp(lp["shared"], h2)
            return d
        return mlp(lp["mlp"], h2)

    def _dense_body(self, x, lp, positions):
        h = x + self._self_attention(lp["attn"], rmsnorm(lp["ln1"], x),
                                     positions)
        return h + self._ffn(lp, rmsnorm(lp["ln2"], h))

    def scan_stacks(self, body, x, params, caches=()):
        """``jax.lax.scan`` of ``body(x, (lp, *cache_l))`` over the layer
        stacks in depth order — a MoE model's leading dense layers, then
        ``layers`` — with ``caches`` (stacked over all layers) split to
        match.  Returns (x, the per-layer outputs stacked over all
        layers)."""
        nd = self.cfg.first_dense_layers
        if not nd:
            return jax.lax.scan(body, x, (params["layers"], *caches))
        x, lead = jax.lax.scan(
            body, x, (params["dense_layers"], *(c[:nd] for c in caches)))
        x, rest = jax.lax.scan(
            body, x, (params["layers"], *(c[nd:] for c in caches)))
        return x, jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                               lead, rest)

    def _run_mixed_stack(self, params, x, positions):
        """Mamba-2 and attention layers in depth order, each followed by
        its FFN (``_mixed_layers``)."""
        lay = params["layers"]

        def mixer(kind, j, h):
            if kind == "attention":
                return attention(jax.tree.map(lambda a: a[j], lay["attn"]),
                                 h, self.attn_cfg, positions)
            return ssm_forward(jax.tree.map(lambda a: a[j], lay["mamba"]),
                               h, self.ssm_cfg)[0]
        return self._mixed_layers(params, x, mixer)

    def _run_dense_stack(self, params, x, positions):
        if self.cfg.mixed:
            return self._run_mixed_stack(params, x, positions)
        body = self._maybe_remat(
            lambda x, xs: (self._dense_body(x, xs[0], positions), None))
        x, _ = self.scan_stacks(body, x, params)
        return x

    def _shared_attn_apply(self, sp, x, positions):
        h = x + attention(sp["attn"], rmsnorm(sp["ln1"], x), self.attn_cfg,
                          positions)
        return h + mlp(sp["mlp"], rmsnorm(sp["ln2"], h))

    def _run_hybrid_stack(self, params, x, positions):
        cfg = self.cfg
        k = cfg.attn_every
        n_seg, rem = divmod(cfg.n_layers, k)

        def ssm_body(x, lp):
            return x + ssm_forward(lp["ssm"], rmsnorm(lp["ln"], x),
                                   self.ssm_cfg)[0], None

        body = self._maybe_remat(ssm_body)
        lp_all = params["ssm_layers"]
        for j in range(n_seg):
            seg = jax.tree.map(lambda a: a[j * k:(j + 1) * k], lp_all)
            x, _ = jax.lax.scan(body, x, seg)
            x = self._shared_attn_apply(params["shared_attn"], x, positions)
        if rem:
            seg = jax.tree.map(lambda a: a[n_seg * k:], lp_all)
            x, _ = jax.lax.scan(body, x, seg)
        return x

    def _run_xlstm_stack(self, params, x, positions):
        n_m, n_s = self._xlstm_counts()
        per_seg = self.cfg.slstm_every - 1 if n_s else n_m

        def m_body(x, lp):
            return x + mlstm_parallel(lp["mlstm"], rmsnorm(lp["ln"], x),
                                      self.xl_cfg), None

        body = self._maybe_remat(m_body)
        mp = params["mlstm_layers"]
        consumed = 0
        for j in range(n_s):
            seg = jax.tree.map(lambda a: a[consumed:consumed + per_seg], mp)
            x, _ = jax.lax.scan(body, x, seg)
            consumed += per_seg
            sp = jax.tree.map(lambda a: a[j], params["slstm_layers"])
            y, _ = slstm_forward(sp["slstm"], rmsnorm(sp["ln"], x),
                                 self.xl_cfg)
            x = x + y
        if consumed < n_m:
            seg = jax.tree.map(lambda a: a[consumed:], mp)
            x, _ = jax.lax.scan(body, x, seg)
        return x

    def _encode(self, params, frames, enc_positions):
        """Encoder over stub frame embeddings (audio frontend)."""
        x = jnp.einsum("bsd,de->bse", frames, params["audio_adapter"]["w"])
        x = constrain(x, ("batch", "seq", "embed"))
        enc_attn = dataclasses.replace(self.attn_cfg, causal=False)

        def body(x, lp):
            h = x + attention(lp["attn"], rmsnorm(lp["ln1"], x), enc_attn,
                              enc_positions)
            return h + mlp(lp["mlp"], rmsnorm(lp["ln2"], h)), None

        x, _ = jax.lax.scan(self._maybe_remat(body), x, params["enc_layers"])
        return x

    def _run_decoder_encdec(self, params, x, positions, enc_out,
                            enc_positions):
        def body(x, lp):
            h = x + attention(lp["attn"], rmsnorm(lp["ln1"], x),
                              self.attn_cfg, positions)
            h = h + attention(lp["xattn"], rmsnorm(lp["lnx"], h),
                              self.attn_cfg, positions, kv_x=enc_out,
                              kv_positions=enc_positions)
            return h + mlp(lp["mlp"], rmsnorm(lp["ln2"], h)), None

        x, _ = jax.lax.scan(self._maybe_remat(body), x, params["dec_layers"])
        return x

    def _trunk(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """Returns (hidden, positions) for the decoder token stream."""
        cfg = self.cfg
        if cfg.family in ("dense", "moe"):
            tokens = batch["tokens"]
            B, S = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
            x = self.embed_tokens(params, tokens)
            x = self._run_dense_stack(params, x, positions)
        elif cfg.family == "vlm":
            patches, tokens = batch["patches"], batch["tokens"]
            B, P, _ = patches.shape
            S = P + tokens.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
            xt = embed(params["embed"], tokens)
            xp = jnp.einsum("bpd,de->bpe", patches,
                            params["vision_adapter"]["w"]).astype(xt.dtype)
            x = jnp.concatenate([xp, xt], axis=1)
            x = constrain(x, ("batch", "seq", "embed"))
            x = self._run_dense_stack(params, x, positions)
        elif cfg.family == "hybrid":
            tokens = batch["tokens"]
            B, S = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
            x = embed(params["embed"], tokens)
            x = self._run_hybrid_stack(params, x, positions)
        elif cfg.family == "xlstm":
            tokens = batch["tokens"]
            B, S = tokens.shape
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
            x = embed(params["embed"], tokens)
            x = self._run_xlstm_stack(params, x, positions)
        elif cfg.family == "encdec":
            frames, tokens = batch["frames"], batch["tokens"]
            B, Se, _ = frames.shape
            S = tokens.shape[1]
            enc_positions = jnp.broadcast_to(
                jnp.arange(Se, dtype=jnp.int32), (B, Se))
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                         (B, S))
            enc_out = self._encode(params, frames, enc_positions)
            x = embed(params["embed"], tokens)
            x = self._run_decoder_encdec(params, x, positions, enc_out,
                                         enc_positions)
        else:
            raise ValueError(cfg.family)
        return x, positions

    def embed_table(self, params):
        """The embedding's {"tok": (vocab, d)}, tied to the head or not."""
        return params["tied_embed" if self.cfg.tie_embeddings else "embed"]

    def embed_tokens(self, params, tokens):
        """Token embeddings times the embedding multiplier (Granite's)."""
        x = embed(self.embed_table(params), tokens)
        m = self.cfg.embedding_multiplier
        return x if m == 1.0 else (x * m).astype(x.dtype)

    def head_weights(self, params):
        """(d, vocab): the output head, or the tied embedding's transpose."""
        if self.cfg.tie_embeddings:
            return {"w": params["tied_embed"]["tok"].T}
        return params["head"]

    def loss(self, params, batch) -> jax.Array:
        x, _ = self._trunk(params, batch)
        x = rmsnorm(params["final_ln"], x, self.cfg.norm_eps)
        labels = batch["labels"]
        if self.cfg.family == "vlm":
            # image prefix carries no LM loss
            P = batch["patches"].shape[1]
            pad = jnp.full(
                (labels.shape[0], P), -1, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
        head = self.head_weights(params)
        if self.cfg.logits_scaling != 1.0:
            head = {"w": head["w"] / self.cfg.logits_scaling}
        return chunked_lm_loss(head, x, labels)

    # ============================================================ serving
    def cache_defs(self, batch: int, seq: int) -> PyTree:
        cfg = self.cfg
        K, dh = cfg.kv_heads, self.attn_cfg.head_dim
        kv_axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        # Sliding-window archs keep a ring buffer of window size — a 500k
        # context costs the same KV memory as a 4k one (§Perf climb #3).
        if cfg.window is not None:
            seq = min(seq, cfg.window)

        def kv(n_layers):
            shape = (n_layers, batch, seq, K, dh)
            return {"k": ArrayDef(shape, kv_axes, cfg.dtype, init="zeros"),
                    "v": ArrayDef(shape, kv_axes, cfg.dtype, init="zeros")}

        if cfg.mixed:
            sc, kinds = self.ssm_cfg, cfg.mixers
            n_m = kinds.count("mamba")
            return {
                "kv": kv(kinds.count("attention")),
                "conv": ArrayDef(
                    (n_m, batch, sc.conv_width - 1, sc.conv_dim),
                    ("layers", "batch", None, None), cfg.dtype,
                    init="zeros"),
                "ssm": ArrayDef(
                    (n_m, batch, sc.state_dim, sc.n_heads, sc.head_dim),
                    ("layers", "batch", None, "ssm_heads", None), F32,
                    init="zeros"),
            }
        if cfg.mla:
            return {"kv": {"lat": ArrayDef(
                (cfg.n_layers, batch, seq, self.mla_cfg.latent_dim),
                ("layers", "batch", "kv_seq", None), cfg.dtype,
                init="zeros")}}
        if cfg.family in ("dense", "moe", "vlm"):
            return {"kv": kv(cfg.n_layers)}
        if cfg.family == "hybrid":
            n_attn = cfg.n_layers // cfg.attn_every
            H = self.ssm_cfg.n_heads
            return {
                "kv": kv(n_attn),
                "conv": ArrayDef(
                    (cfg.n_layers, batch, self.ssm_cfg.conv_width - 1,
                     self.ssm_cfg.conv_dim),
                    ("layers", "batch", None, None), cfg.dtype,
                    init="zeros"),
                "ssm": ArrayDef(
                    (cfg.n_layers, batch, cfg.ssm_state, H, cfg.ssm_head_dim),
                    ("layers", "batch", None, "ssm_heads", None), F32,
                    init="zeros"),
            }
        if cfg.family == "xlstm":
            n_m, n_s = self._xlstm_counts()
            H, dhx = self.xl_cfg.n_heads, self.xl_cfg.head_dim
            dhs = cfg.d_model // H
            out = {
                "C": ArrayDef((n_m, batch, H, dhx, dhx),
                              ("layers", "batch", None, "d_inner", None), F32,
                              init="zeros"),
                "n": ArrayDef((n_m, batch, H, dhx),
                              ("layers", "batch", None, "d_inner"), F32,
                              init="zeros"),
            }
            if n_s:
                for nm in ("sc", "sn", "sh", "sm"):
                    out[nm] = ArrayDef((n_s, batch, H, dhs),
                                       ("layers", "batch", None, None), F32,
                                       init="zeros")
            return out
        if cfg.family == "encdec":
            enc_seq = seq  # encoder memory length == prompt frames
            return {
                "kv": kv(cfg.n_layers),
                "xk": ArrayDef((cfg.n_layers, batch, enc_seq, K, dh),
                               kv_axes, cfg.dtype, init="zeros"),
                "xv": ArrayDef((cfg.n_layers, batch, enc_seq, K, dh),
                               kv_axes, cfg.dtype, init="zeros"),
            }
        raise ValueError(cfg.family)

    def init_cache(self, batch: int, seq: int) -> PyTree:
        return jax.tree.map(
            lambda d: jnp.zeros(d.shape, d.dtype),
            self.cache_defs(batch, seq),
            is_leaf=lambda x: isinstance(x, ArrayDef),
        )

    # one-token decode ------------------------------------------------------
    def decode(self, params, cache, tokens, pos):
        """tokens: (B,1) int32; pos: scalar int32 (same for all rows).
        Returns (logits (B, vocab), new cache)."""
        cfg = self.cfg
        x = self.embed_tokens(params, tokens)
        if cfg.mixed:
            x, cache = self._decode_mixed(params, cache, x, pos)
        elif cfg.mla:
            def body(x, xs):
                lp, cl = xs
                y, cl = mla_attention_decode(
                    lp["attn"], rmsnorm(lp["ln1"], x), cl, pos,
                    self.mla_cfg)
                x = x + y
                return x + self._ffn(lp, rmsnorm(lp["ln2"], x),
                                     decode=True), (cl,)

            x, (nl,) = self.scan_stacks(body, x, params,
                                         (cache["kv"]["lat"],))
            cache = {**cache, "kv": {"lat": nl}}
        elif cfg.family in ("dense", "moe", "vlm"):
            ring = (cfg.window is not None
                    and cache["kv"]["k"].shape[2] <= cfg.window)

            def body(x, xs):
                lp, ck, cv = xs
                h = rmsnorm(lp["ln1"], x)
                dec = attention_decode_ring if ring else attention_decode
                y, ck, cv = dec(lp["attn"], h, ck, cv, pos, self.attn_cfg)
                x = x + y
                h2 = rmsnorm(lp["ln2"], x)
                # moe_decode == moe: decode shares the routing function
                # and grouped GEMM with prefill, so a token's expert
                # assignment never depends on how the stream is chunked.
                x = x + self._ffn(lp, h2, decode=True)
                return x, (ck, cv)

            x, (nk, nv) = self.scan_stacks(
                body, x, params, (cache["kv"]["k"], cache["kv"]["v"]))
            cache = {**cache, "kv": {"k": nk, "v": nv}}
        elif cfg.family == "hybrid":
            x, cache = self._decode_hybrid(params, cache, x, pos)
        elif cfg.family == "xlstm":
            x, cache = self._decode_xlstm(params, cache, x)
        elif cfg.family == "encdec":
            def body(x, xs):
                lp, ck, cv, xk, xv = xs
                h = rmsnorm(lp["ln1"], x)
                y, ck, cv = attention_decode(lp["attn"], h, ck, cv, pos,
                                             self.attn_cfg)
                x = x + y
                hx = rmsnorm(lp["lnx"], x)
                x = x + cross_attention_decode(lp["xattn"], hx, xk, xv,
                                               self.attn_cfg)
                x = x + mlp(lp["mlp"], rmsnorm(lp["ln2"], x))
                return x, (ck, cv)

            x, (nk, nv) = jax.lax.scan(
                body, x,
                (params["dec_layers"], cache["kv"]["k"], cache["kv"]["v"],
                 cache["xk"], cache["xv"]))
            cache = {**cache, "kv": {"k": nk, "v": nv}}
        else:
            raise ValueError(cfg.family)
        return self._logits(params, x)[:, 0], cache

    def _logits(self, params, x):
        """The final norm and the head (Granite's logits scaling)."""
        x = rmsnorm(params["final_ln"], x, self.cfg.norm_eps)
        logits = lm_head(self.head_weights(params), x)
        if self.cfg.logits_scaling != 1.0:
            logits = logits / self.cfg.logits_scaling
        return logits

    def _mixed_layers(self, params, x, mixer):
        """A Mamba-2/attention stack unrolled in depth order:
        ``mixer(kind, j, h)`` is the mixer of the layer (``j``-th of its
        kind) on its normed input; each sublayer's output is scaled by the
        residual multiplier."""
        cfg = self.cfg
        lay, eps, rm = params["layers"], cfg.norm_eps, cfg.residual_multiplier
        idx = {"attention": 0, "mamba": 0}
        for l, kind in enumerate(cfg.mixers):
            lp = jax.tree.map(lambda a: a[l], {
                k: v for k, v in lay.items() if k not in ("attn", "mamba")})
            j = idx[kind]
            idx[kind] += 1
            y = mixer(kind, j, rmsnorm(lp["ln1"], x, eps))
            x = x + (y * rm).astype(x.dtype)
            x = x + (self._ffn(lp, rmsnorm(lp["ln2"], x, eps)) * rm
                     ).astype(x.dtype)
        return x

    def _decode_mixed(self, params, cache, x, pos):
        lay = params["layers"]
        new = {"k": [], "v": [], "conv": [], "ssm": []}

        def mixer(kind, j, h):
            if kind == "attention":
                y, ck, cv = attention_decode(
                    jax.tree.map(lambda a: a[j], lay["attn"]), h,
                    cache["kv"]["k"][j], cache["kv"]["v"][j], pos,
                    self.attn_cfg)
                new["k"].append(ck)
                new["v"].append(cv)
                return y
            y, cs, ss = ssm_decode(jax.tree.map(lambda a: a[j], lay["mamba"]),
                                   h, cache["conv"][j], cache["ssm"][j],
                                   self.ssm_cfg)
            new["conv"].append(cs)
            new["ssm"].append(ss)
            return y

        x = self._mixed_layers(params, x, mixer)
        return x, {"kv": {"k": jnp.stack(new["k"]), "v": jnp.stack(new["v"])},
                   "conv": jnp.stack(new["conv"]),
                   "ssm": jnp.stack(new["ssm"])}

    def _prefill_mixed(self, params, batch, cache):
        cfg, lay = self.cfg, params["layers"]
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self.embed_tokens(params, tokens)
        pad = cache["kv"]["k"].shape[2] - S
        new = {"k": [], "v": [], "conv": [], "ssm": []}

        def mixer(kind, j, h):
            if kind == "attention":
                p = jax.tree.map(lambda a: a[j], lay["attn"])
                for name in ("k", "v"):
                    t = jnp.einsum("bsd,dhk->bshk", h, p["w" + name])
                    if name == "k" and cfg.use_rope:
                        t = rope(t, positions, cfg.rope_theta)
                    new[name].append(jnp.pad(
                        t, ((0, 0), (0, pad), (0, 0), (0, 0))).astype(
                            cache["kv"][name].dtype))
                return attention(p, h, self.attn_cfg, positions)
            y, cs, ss = ssm_forward(
                jax.tree.map(lambda a: a[j], lay["mamba"]), h, self.ssm_cfg)
            new["conv"].append(cs.astype(cache["conv"].dtype))
            new["ssm"].append(ss)
            return y

        x = self._mixed_layers(params, x, mixer)
        cache = {"kv": {"k": jnp.stack(new["k"]), "v": jnp.stack(new["v"])},
                 "conv": jnp.stack(new["conv"]),
                 "ssm": jnp.stack(new["ssm"])}
        return self._logits(params, x[:, -1:])[:, 0], cache

    def _decode_hybrid(self, params, cache, x, pos):
        cfg = self.cfg
        k = cfg.attn_every
        n_seg, rem = divmod(cfg.n_layers, k)

        def seg_scan(x, lp_seg, conv_seg, ssm_seg):
            def body(x, xs):
                lp, cs, ss = xs
                y, cs, ss = ssm_decode(lp["ssm"], rmsnorm(lp["ln"], x), cs,
                                       ss, self.ssm_cfg)
                return x + y, (cs, ss)

            x, (nc, ns) = jax.lax.scan(body, x, (lp_seg, conv_seg, ssm_seg))
            return x, nc, ns

        lp_all = params["ssm_layers"]
        conv_all, ssm_all = cache["conv"], cache["ssm"]
        new_conv, new_ssm = [], []
        kcache, vcache = cache["kv"]["k"], cache["kv"]["v"]
        new_k, new_v = [], []
        sp = params["shared_attn"]
        for j in range(n_seg):
            sl = slice(j * k, (j + 1) * k)
            lp_seg = jax.tree.map(lambda a: a[sl], lp_all)
            x, nc, ns = seg_scan(x, lp_seg, conv_all[sl], ssm_all[sl])
            new_conv.append(nc)
            new_ssm.append(ns)
            h = rmsnorm(sp["ln1"], x)
            y, ck, cv = attention_decode(sp["attn"], h, kcache[j], vcache[j],
                                         pos, self.attn_cfg)
            x = x + y
            x = x + mlp(sp["mlp"], rmsnorm(sp["ln2"], x))
            new_k.append(ck)
            new_v.append(cv)
        if rem:
            sl = slice(n_seg * k, cfg.n_layers)
            lp_seg = jax.tree.map(lambda a: a[sl], lp_all)
            x, nc, ns = seg_scan(x, lp_seg, conv_all[sl], ssm_all[sl])
            new_conv.append(nc)
            new_ssm.append(ns)
        cache = {
            "kv": {"k": jnp.stack(new_k), "v": jnp.stack(new_v)},
            "conv": jnp.concatenate(new_conv, axis=0),
            "ssm": jnp.concatenate(new_ssm, axis=0),
        }
        return x, cache

    def _decode_xlstm(self, params, cache, x):
        n_m, n_s = self._xlstm_counts()
        per_seg = self.cfg.slstm_every - 1 if n_s else n_m
        mp = params["mlstm_layers"]

        def seg_scan(x, lp_seg, C_seg, n_seg_state):
            def body(x, xs):
                lp, C, n = xs
                y, C, n = mlstm_decode(lp["mlstm"], rmsnorm(lp["ln"], x), C,
                                       n, self.xl_cfg)
                return x + y, (C, n)

            x, (nC, nn) = jax.lax.scan(body, x, (lp_seg, C_seg, n_seg_state))
            return x, nC, nn

        new_C, new_n = [], []
        new_s = {nm: [] for nm in ("sc", "sn", "sh", "sm")}
        consumed = 0
        for j in range(n_s):
            sl = slice(consumed, consumed + per_seg)
            lp_seg = jax.tree.map(lambda a: a[sl], mp)
            x, nC, nn = seg_scan(x, lp_seg, cache["C"][sl], cache["n"][sl])
            new_C.append(nC)
            new_n.append(nn)
            consumed += per_seg
            sp = jax.tree.map(lambda a: a[j], params["slstm_layers"])
            state = tuple(cache[nm][j] for nm in ("sc", "sn", "sh", "sm"))
            y, state = slstm_decode(sp["slstm"], rmsnorm(sp["ln"], x), state,
                                    self.xl_cfg)
            x = x + y
            for nm, s in zip(("sc", "sn", "sh", "sm"), state):
                new_s[nm].append(s)
        if consumed < n_m:
            sl = slice(consumed, n_m)
            lp_seg = jax.tree.map(lambda a: a[sl], mp)
            x, nC, nn = seg_scan(x, lp_seg, cache["C"][sl], cache["n"][sl])
            new_C.append(nC)
            new_n.append(nn)
        cache = {"C": jnp.concatenate(new_C, 0),
                 "n": jnp.concatenate(new_n, 0)}
        if n_s:
            for nm in ("sc", "sn", "sh", "sm"):
                cache[nm] = jnp.stack(new_s[nm])
        return x, cache

    # prefill ---------------------------------------------------------------
    def prefill(self, params, batch, cache):
        """Consume the prompt, fill the cache, return last-position logits.

        For recurrent families the cache holds the final state (the parallel
        forms return their final recurrence states); for attention families
        the prompt's K/V land in the cache (ring-ified for windowed archs).
        """
        cfg = self.cfg
        if cfg.mixed:
            return self._prefill_mixed(params, batch, cache)
        if cfg.family in ("dense", "moe", "vlm", "encdec"):
            return self._prefill_attn(params, batch, cache)
        if cfg.family == "hybrid":
            return self._prefill_hybrid(params, batch, cache)
        return self._prefill_xlstm(params, batch, cache)

    def _prefill_hybrid(self, params, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = embed(params["embed"], tokens)
        k = cfg.attn_every
        n_seg, rem = divmod(cfg.n_layers, k)
        acfg = self.attn_cfg

        def body(x, lp):
            out, conv_s, ssm_s = ssm_forward(
                lp["ssm"], rmsnorm(lp["ln"], x), self.ssm_cfg)
            return x + out, (conv_s, ssm_s)

        body = self._maybe_remat(body)
        lp_all = params["ssm_layers"]
        sp = params["shared_attn"]
        convs, ssms, att_k, att_v = [], [], [], []
        S_cache = cache["kv"]["k"].shape[2]
        for j in range(n_seg):
            seg = jax.tree.map(lambda a: a[j * k:(j + 1) * k], lp_all)
            x, (cs, ss) = jax.lax.scan(body, x, seg)
            convs.append(cs)
            ssms.append(ss)
            h = rmsnorm(sp["ln1"], x)
            kk = jnp.einsum("bsd,dhk->bshk", h, sp["attn"]["wk"])
            vv = jnp.einsum("bsd,dhk->bshk", h, sp["attn"]["wv"])
            kk = rope(kk, positions, acfg.rope_theta)
            # place prompt K/V at the head of the cache-length buffer
            pad = S_cache - S
            if pad > 0:
                kk = jnp.pad(kk, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vv = jnp.pad(vv, ((0, 0), (0, pad), (0, 0), (0, 0)))
            att_k.append(kk.astype(cache["kv"]["k"].dtype))
            att_v.append(vv.astype(cache["kv"]["v"].dtype))
            x = x + attention(sp["attn"], h, acfg, positions)
            x = x + mlp(sp["mlp"], rmsnorm(sp["ln2"], x))
        if rem:
            seg = jax.tree.map(lambda a: a[n_seg * k:], lp_all)
            x, (cs, ss) = jax.lax.scan(body, x, seg)
            convs.append(cs)
            ssms.append(ss)
        new_cache = {
            "kv": {"k": jnp.stack(att_k), "v": jnp.stack(att_v)},
            "conv": jnp.concatenate(convs, 0).astype(cache["conv"].dtype),
            "ssm": jnp.concatenate(ssms, 0),
        }
        x = rmsnorm(params["final_ln"], x)
        logits = lm_head(params["head"], x[:, -1:])[:, 0]
        return logits, new_cache

    def _prefill_xlstm(self, params, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = embed(params["embed"], tokens)
        n_m, n_s = self._xlstm_counts()
        per_seg = cfg.slstm_every - 1 if n_s else n_m

        def m_body(x, lp):
            out, C_f, n_f = mlstm_parallel(
                lp["mlstm"], rmsnorm(lp["ln"], x), self.xl_cfg,
                return_state=True)
            return x + out, (C_f, n_f)

        m_body = self._maybe_remat(m_body)
        mp = params["mlstm_layers"]
        Cs, ns = [], []
        s_states = {nm: [] for nm in ("sc", "sn", "sh", "sm")}
        consumed = 0
        for j in range(n_s):
            seg = jax.tree.map(lambda a: a[consumed:consumed + per_seg], mp)
            x, (C_f, n_f) = jax.lax.scan(m_body, x, seg)
            Cs.append(C_f)
            ns.append(n_f)
            consumed += per_seg
            spj = jax.tree.map(lambda a: a[j], params["slstm_layers"])
            y, state = slstm_forward(spj["slstm"], rmsnorm(spj["ln"], x),
                                     self.xl_cfg)
            x = x + y
            for nm, st in zip(("sc", "sn", "sh", "sm"), state):
                s_states[nm].append(st)
        if consumed < n_m:
            seg = jax.tree.map(lambda a: a[consumed:], mp)
            x, (C_f, n_f) = jax.lax.scan(m_body, x, seg)
            Cs.append(C_f)
            ns.append(n_f)
        new_cache = {"C": jnp.concatenate(Cs, 0), "n": jnp.concatenate(ns, 0)}
        if n_s:
            for nm in ("sc", "sn", "sh", "sm"):
                new_cache[nm] = jnp.stack(s_states[nm])
        x = rmsnorm(params["final_ln"], x)
        logits = lm_head(params["head"], x[:, -1:])[:, 0]
        return logits, new_cache

    def _prefill_attn(self, params, batch, cache):
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = embed(params["embed"], tokens)
        acfg = self.attn_cfg

        def kv_of(lp, h, pos_b):
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            k = rope(k, pos_b, acfg.rope_theta)
            return k, v

        if cfg.family == "encdec":
            frames = batch["frames"]
            Se = frames.shape[1]
            enc_positions = jnp.broadcast_to(
                jnp.arange(Se, dtype=jnp.int32), (B, Se))
            enc_out = self._encode(params, frames, enc_positions)

            def body(x, lp):
                h = rmsnorm(lp["ln1"], x)
                k, v = kv_of(lp["attn"], h, positions)
                x = x + attention(lp["attn"], h, acfg, positions)
                hx = rmsnorm(lp["lnx"], x)
                xk = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wk"])
                xv = jnp.einsum("bsd,dhk->bshk", enc_out, lp["xattn"]["wv"])
                x = x + attention(lp["xattn"], hx, acfg, positions,
                                  kv_x=enc_out, kv_positions=enc_positions)
                x = x + mlp(lp["mlp"], rmsnorm(lp["ln2"], x))
                return x, (k, v, xk, xv)

            x, (ks, vs, xks, xvs) = jax.lax.scan(
                self._maybe_remat(body), x, params["dec_layers"])
            cache = {"kv": {"k": ks, "v": vs}, "xk": xks, "xv": xvs}
        elif cfg.mla:
            def body(x, xs):
                lp = xs[0]
                h = rmsnorm(lp["ln1"], x)
                _, _, lat = mla_project(lp["attn"], h, positions,
                                        self.mla_cfg)
                x = x + self._self_attention(lp["attn"], h, positions)
                return x + self._ffn(lp, rmsnorm(lp["ln2"], x)), (lat,)

            x, (lats,) = self.scan_stacks(self._maybe_remat(body), x,
                                           params)
            cache = {**cache, "kv": {"lat": lats}}
        else:
            def body(x, xs):
                lp = xs[0]
                h = rmsnorm(lp["ln1"], x)
                k, v = kv_of(lp["attn"], h, positions)
                x = x + attention(lp["attn"], h, acfg, positions)
                h2 = rmsnorm(lp["ln2"], x)
                x = x + self._ffn(lp, h2)
                return x, (k, v)

            x, (ks, vs) = self.scan_stacks(self._maybe_remat(body), x,
                                            params)
            if cfg.window is not None and S > cfg.window:
                # Ring-ify: only the last `window` tokens matter; place each
                # at its ring slot (pos % W) so decode continues seamlessly.
                W = cfg.window
                slots = jnp.mod(jnp.arange(S - W, S), W)
                ks = jnp.zeros_like(ks[:, :, :W]).at[:, :, slots].set(
                    ks[:, :, S - W:])
                vs = jnp.zeros_like(vs[:, :, :W]).at[:, :, slots].set(
                    vs[:, :, S - W:])
            cache = {**cache, "kv": {"k": ks, "v": vs}}
        x = rmsnorm(params["final_ln"], x)
        logits = lm_head(params["head"], x[:, -1:])[:, 0]
        return logits, cache

    # ======================================================== input specs
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input of the step the
        shape exercises (train/prefill -> batch dict; decode -> tokens/pos +
        cache)."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind in ("train", "prefill"):
            batch: Dict[str, Any] = {}
            if cfg.family == "encdec":
                batch["frames"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                       cfg.dtype)
                batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            elif cfg.family == "vlm":
                P = cfg.frontend_tokens
                batch["patches"] = jax.ShapeDtypeStruct((B, P, cfg.d_model),
                                                        cfg.dtype)
                batch["tokens"] = jax.ShapeDtypeStruct((B, S - P), i32)
            else:
                batch["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
            if shape.kind == "train":
                lbl_len = S if cfg.family != "vlm" else S - cfg.frontend_tokens
                batch["labels"] = jax.ShapeDtypeStruct((B, lbl_len), i32)
            return batch
        # decode
        cache = jax.tree.map(
            lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype),
            self.cache_defs(B, S),
            is_leaf=lambda x: isinstance(x, ArrayDef),
        )
        return {
            "tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "pos": jax.ShapeDtypeStruct((), i32),
            "cache": cache,
        }
