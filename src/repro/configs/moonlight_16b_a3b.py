"""moonlight-16b-a3b — Moonlight-16B-A3B (Moonshot AI), the DeepSeek-V3
block at hidden size 2048:
https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json

27 layers: layer 0 has a dense SwiGLU of width 11264
(``first_k_dense_replace`` 1), layers 1-26 have 64 routed experts of width
1408, 6 per token, plus 2 shared experts. Attention is MLA with no query
bottleneck (``q_lora_rank`` null): kv_lora_rank 512, 16 heads of
qk_nope 128 + qk_rope 64, v 128. The router scores each expert by a
sigmoid and chooses by score plus a per-expert correction bias
(``noaux_tc``; ``n_group`` 1 = no group limit), renormalises the chosen
scores and scales them by 2.446. RMSNorm eps 1e-5, rope_theta 50000,
vocab 163840, untied embeddings. 15.96 B parameters, 2.91 B active.

Departures: RoPE rotates the two halves of the 64 rope lanes
(rotate-half) where the checkpoint interleaves them, a fixed permutation of
those columns that random weights cannot tell apart; the multi-token
prediction head is not in this config (``num_nextn_predict_layers`` 0).
"""
from repro.models.config import MLAConfig, MoEConfig, ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, vocab_size=163840,
        n_heads=16, n_kv_heads=16, head_dim=128,
        d_ff=11264,  # the leading dense layer
        n_dense_layers=1,
        layer_pattern=("attn",),
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, rope_head_dim=64,
                      nope_head_dim=128, v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                      capacity_factor=1.25, scoring="sigmoid",
                      routed_scaling_factor=2.446),
        rope_theta=50_000.0, rms_eps=1e-5,
    )


def smoke_config() -> ModelConfig:
    """Every mechanism at CPU size: one dense layer, then MoE layers; MLA
    without a query bottleneck; 8 sigmoid-routed experts with a bias and
    2 shared experts."""
    return ModelConfig(
        name="moonlight-smoke", family="moe",
        n_layers=3, d_model=64, vocab_size=512,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
        n_dense_layers=1,
        layer_pattern=("attn",),
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16, rope_head_dim=8,
                      nope_head_dim=16, v_head_dim=16),
        moe=MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=2,
                      capacity_factor=8.0, scoring="sigmoid",
                      routed_scaling_factor=2.446),
        rope_theta=50_000.0, rms_eps=1e-5,
        dtype="float32", kv_chunk=64,
    )
