"""deepseek-v3 [moe, latent attention]: DeepSeek-V3
(huggingface.co/deepseek-ai/DeepSeek-V3, config.json).

61 layers, d_model 7168, 128 heads of multi-head latent attention in every
layer: queries through a 1536-wide latent, keys and values through one
cached 512-wide latent beside a 64-wide RoPE key shared by all heads, 128
non-RoPE and 64 RoPE query/key dims and 128 value dims a head.  The first 3
layers have a dense SwiGLU of 18432; the other 58 have 256 routed experts of
2048, top-8 (sigmoid scores, 8 groups, top-4 groups), and one shared expert
of 2048.  One multi-token-prediction module (a concat projection, one more
layer, the shared LM head) runs in training.

The oracle decomposes, scores and ranks it (``core/network.py``); the model
zoo does not build it, so it is not in ``ARCHS``.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=2048,
    vocab=129280,
    rope_theta=10000.0,
    norm_eps=1e-6,
    moe_experts=256,
    moe_top_k=8,
    moe_shared_d_ff=2048,
    moe_dense_layers=3,
    moe_dense_d_ff=18432,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    mtp_layers=1,
)
