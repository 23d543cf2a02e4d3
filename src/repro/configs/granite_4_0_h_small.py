"""granite-4.0-h-small [hf:ibm-granite/granite-4.0-h-small, 32B-A9B].

40L d_model=4096: 36 Mamba-2 mixers (128 heads of 64, d_state 128, one B/C
group, conv width 4 with bias) and 4 NoPE GQA layers (32 heads, 8 KV heads
of 128) at indices 5, 15, 25 and 35; after every mixer 72 routed experts
of width 768 (top-10, softmax over the ten logits) beside one shared
SwiGLU of width 1536.  Vocabulary 100,352, tied embedding; Granite's
multipliers (embedding x12, residual x0.22, attention scale 1/128, logits
/16), RMSNorm eps 1e-5."""

import dataclasses

from ..models.config import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    arch="granite_4_0_h_small",
    family="moe",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab=100352,
    n_experts=72,
    top_k=10,
    moe_dispatch="dropless",
    n_shared_experts=2,                  # shared_intermediate_size 1536
    layer_types=_PERIOD * 4,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_conv=4,
    ssm_conv_bias=True,
    ssm_chunk=256,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attention_multiplier=0.0078125,
    use_rope=False,
    tie_embeddings=True,
    norm_eps=1e-5,
)

SMOKE = dataclasses.replace(
    CONFIG, n_layers=4, d_model=64, n_heads=4, kv_heads=2, head_dim=16,
    d_ff=32, vocab=256, n_experts=8, top_k=3, ssm_state=16,
    ssm_head_dim=16, ssm_chunk=8,
    layer_types=("mamba", "attention", "mamba", "mamba"),
)
