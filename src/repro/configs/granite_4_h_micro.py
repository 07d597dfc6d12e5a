"""granite-4-h-micro — Mamba-2/attention hybrid, 36 mixers and 4 NoPE GQA
layers in periods of 10, each layer followed by a SwiGLU MLP.
[hf:ibm-granite/granite-4.0-h-micro]"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,             # shared_intermediate_size
    vocab_size=100352,
    mlp_variant="swiglu",
    ssm_state=128,
    ssm_head_dim=64,       # 64 heads × 64 = d_inner 4096 (expand 2)
    ssm_expand=2,
    conv_width=4,
    chunk_size=256,
    layer_pattern="mmmmmammmm",   # attention at layers 5, 15, 25, 35
    use_rope=False,        # position_embedding_type "nope"
    attn_scale=0.015625,   # attention_multiplier
    embed_scale=12.0,      # embedding_multiplier
    residual_scale=0.22,   # residual_multiplier
    norm_eps=1e-5,
)
