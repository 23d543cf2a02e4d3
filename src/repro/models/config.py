"""ModelConfig — one dataclass describing every supported architecture
family (dense / MoE / SSM / hybrid / xLSTM / enc-dec / VLM / audio)."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch: str
    family: str                 # dense | moe | hybrid | xlstm | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    window: Optional[int] = None          # sliding-window attention (Mixtral)
    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_parallelism: str = "tp"           # "tp" | "ep"
    moe_dispatch: str = "dropless"        # "dropless" | "capacity"
    moe_ep_axis_size: int = 16            # ep expert-pad target; must be a
                                          # multiple of the mesh model axis
    capacity_factor: float = 1.0          # capacity path only
    # False: the top-k gates keep their softmax mass (DeepSeek-V2);
    # True renormalizes them to sum to one (Mixtral, granite-MoE).
    norm_topk_prob: bool = True
    # Always-on shared experts (DeepSeekMoE): one SwiGLU of width
    # n_shared_experts * d_ff beside the routed ones, in every MoE layer.
    n_shared_experts: int = 0
    # Leading dense layers (DeepSeek's first_k_dense_replace): SwiGLU of
    # width dense_d_ff in place of the experts; n_layers counts them.
    first_dense_layers: int = 0
    dense_d_ff: int = 0
    # Multi-head latent attention (DeepSeek-V2 Sec. 2.1), on when
    # kv_lora_rank > 0: keys and values come from one cached latent of
    # kv_lora_rank + qk_rope_head_dim values per token; head_dim is
    # qk_nope_head_dim + qk_rope_head_dim, the query's width.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling of the rope dims (None: plain rope).
    yarn: Optional["Yarn"] = None
    # SSM / hybrid
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_groups: int = 1                   # Mamba-2 B/C groups (n_groups)
    ssm_conv: int = 4                     # causal conv width over x, B, C
    ssm_conv_bias: bool = True
    ssm_chunk: int = 256                  # SSD chunk of the parallel form
    attn_every: int = 0                   # hybrid: shared attn every k layers
    # A decoder whose layers differ by mixer (granite-4.0-H): one entry per
    # layer, "attention" or "mamba"; every layer keeps its FFN (the MoE of
    # a "moe" family).  None: every layer is attention.
    layer_types: Optional[Tuple[str, ...]] = None
    # Experts whose weights this chip holds (the first n_local_experts of
    # n_experts, the chip's expert-parallel share; 0: all).  The router
    # keeps all n_experts outputs; picks of experts held elsewhere add
    # nothing here.
    n_local_experts: int = 0
    # Granite's multipliers and positions: the embedding is scaled by
    # embedding_multiplier, each sublayer's output by residual_multiplier
    # before the residual add, the logits divided by logits_scaling;
    # attention_multiplier replaces 1/sqrt(head_dim) as the softmax scale;
    # use_rope False is NoPE attention.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    use_rope: bool = True
    tie_embeddings: bool = False          # the head is the embedding's
    norm_eps: float = 1e-6                # RMSNorm eps (the hybrid path)
    # xLSTM
    slstm_every: int = 0                  # one sLSTM per k layers
    # enc-dec
    enc_layers: int = 0
    # modality frontend stubs
    frontend: Optional[str] = None        # "audio" | "vision"
    frontend_tokens: int = 0              # patches / frames in the prefix
    dtype: object = jnp.bfloat16
    # training
    remat: bool = True

    def __post_init__(self):
        if self.layer_types is not None:      # a JSON list, say
            object.__setattr__(self, "layer_types", tuple(self.layer_types))

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def mixers(self) -> Tuple[str, ...]:
        """Each layer's mixer, "attention" or "mamba"."""
        return self.layer_types or ("attention",) * self.n_layers

    @property
    def mixed(self) -> bool:
        """A stack of Mamba-2 and attention layers (``layer_types``)."""
        return "mamba" in self.mixers

    @property
    def held_experts(self) -> int:
        return self.n_local_experts or self.n_experts

    @property
    def moe_layers(self) -> int:
        """Layers whose FFN is the routed experts."""
        return self.n_layers - self.first_dense_layers

    def validate(self) -> "ModelConfig":
        assert self.n_heads % max(self.kv_heads, 1) == 0, "GQA grouping"
        if self.family == "moe":
            assert self.n_experts > 0 and self.top_k > 0
            assert self.moe_dispatch in ("dropless", "capacity"), \
                self.moe_dispatch
            if self.moe_parallelism == "ep":
                assert self.moe_ep_axis_size > 0, self.moe_ep_axis_size
        if self.first_dense_layers:
            assert self.family == "moe" and self.dense_d_ff > 0
            assert 0 < self.first_dense_layers < self.n_layers
        if self.mla:
            assert (self.resolved_head_dim
                    == self.qk_nope_head_dim + self.qk_rope_head_dim), \
                "MLA head_dim is qk_nope_head_dim + qk_rope_head_dim"
            assert self.qk_rope_head_dim % 2 == 0 and self.v_head_dim > 0
        if self.layer_types is not None:
            assert len(self.layer_types) == self.n_layers, \
                "layer_types names every layer"
            assert set(self.layer_types) <= {"attention", "mamba"}
            assert not self.first_dense_layers and not self.mla
        if self.mixed:
            assert self.family == "moe" and self.ssm_state > 0
            assert self.d_inner % self.ssm_head_dim == 0
            assert (self.d_inner // self.ssm_head_dim) % self.ssm_groups == 0
        if self.n_local_experts:
            assert 0 < self.n_local_experts <= self.n_experts
            assert self.moe_parallelism == "tp"
        if self.family == "hybrid":
            assert self.ssm_state > 0 and self.attn_every > 0
        if self.family == "encdec":
            assert self.enc_layers > 0
        return self


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2's
    ``rope_scaling`` states it."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                   # "train" | "prefill" | "decode"


# The assigned input-shape set (identical for every LM arch).
SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# Smoke-scale shapes for CPU tests.
SMOKE_SHAPES = {
    "train_4k": ShapeConfig("train_4k", 128, 4, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 128, 2, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 128, 4, "decode"),
    "long_500k": ShapeConfig("long_500k", 256, 1, "decode"),
}
