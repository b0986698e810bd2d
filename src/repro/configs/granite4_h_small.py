"""granite-4.0-h-small — IBM Granite 4.0-H Small, 32B total / 9B active
(HF ``granitemoehybrid``; config.json of ibm-granite/granite-4.0-h-small).

40L d_model=4096 vocab=100352, tied embeddings, RMSNorm eps 1e-5.  Period
10: Mamba-2 mixers at positions 0-4 and 6-9, GQA attention (32 q / 8 kv
heads x 128) at position 5, so 36 Mamba-2 and 4 attention layers.  The
attention has no positional encoding (NoPE) and scales its scores by
``attention_multiplier`` = 1/128.  Mamba-2: 128 heads x 64 (expand 2),
d_state 128, one B/C group, d_conv 4, chunk 256.  EVERY layer's FFN is a
dropless MoE: 72 routed SwiGLU experts of width 768, top-10 with softmax
over the ten logits, plus one shared SwiGLU expert of width 1536 added
unweighted on the same normed input.  Scaling: x = 12 E[t]; each sublayer
adds 0.22 x its output; logits / 16.
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=768,
    vocab_size=100352,
    mlp_variant="swiglu",
    norm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    attn_scale=1.0 / 128,
    use_rope=False,
    moe_num_experts=72,
    moe_top_k=10,
    moe_d_ff=768,
    moe_shared_expert=True,
    moe_shared_d_ff=1536,
    attn_every=10,
    attn_offset=5,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_conv=4,
    ssm_chunk=256,
    param_dtype="bfloat16",
    compute_dtype="bfloat16",
)
