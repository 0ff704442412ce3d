"""granite-4.0-h-small — Mamba-2 / NoPE-attention hybrid with an MoE FFN in
every layer [hf:ibm-granite/granite-4.0-h-small config.json].

40L d_model=4096; 36 Mamba-2 layers (128 heads × 64, d_state 128, one
group, conv 4) and 4 GQA attention layers (32 heads, 8 KV heads, head_dim
128) at layers 5, 15, 25, 35, with no positional embedding and softmax
scale ``attention_multiplier`` 1/128.  Every layer's FFN: 72 routed SwiGLU
experts of width 768, top-10 (softmax over the ten logits), beside one
shared SwiGLU MLP of width 1536.  Embeddings × 12, each sublayer's output
× 0.22 before its residual add, logits ÷ 16; vocab 100352, tied.
"""
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="granite-4.0-h-small", family="hybrid",
        n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8,
        d_ff=768, vocab=100352, head_dim=128,
        use_rope=False, attention_multiplier=0.0078125,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=16.0,
        activation="silu", glu=True, tie_embeddings=True,
        n_experts=72, top_k=10, n_shared_experts=1, shared_d_ff=1536,
        ssm_state=128, ssm_conv=4, ssm_head_dim=64, ssm_expand=2,
        hybrid_period=10, hybrid_attn_index=5, hybrid_moe_every=1,
    )
