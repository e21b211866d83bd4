"""granite-4.0-h-small [hybrid] — 40 layers in periods of 10 (5 Mamba-2,
1 NoPE GQA attention, 4 Mamba-2), a dropless 72-expert top-10 MoE with a
shared SwiGLU expert in every layer, granite's embedding, residual and
logit multipliers, tied embeddings
[hf:ibm-granite/granite-4.0-h-small, model_type granitemoehybrid]."""
from ..models.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=768, vocab_size=100352, tie_embeddings=True, rms_eps=1e-5,
    rope_style="none", attn_scale=0.0078125,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=16.0,
    n_experts=72, n_experts_active=10, moe_period=1, moe_dispatch="dropless",
    shared_ff=1536,
    ssm_kind="mamba2", ssm_state=128, ssm_conv=4, ssm_expand=2,
    ssm_heads=128, ssm_head_dim=64, ssm_groups=1, ssm_chunk=256,
    attn_period=10, attn_offset=5,
    notes="d_ff is the width of one routed expert (config intermediate_size).",
)
