"""nemotron-3-nano-30b-a3b [hybrid by layer pattern]: NVIDIA Nemotron-3-Nano-30B-A3B
(huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json).

52 layers in the published ``hybrid_override_pattern``: 23 Mamba-2 mixers
(64 heads of 64, state 128, 8 groups, conv 4, chunk 128), 23 mixtures of 128
relu² experts of width 1856, top-6, with one shared expert of width 3712 and
no attention in front of them, and 6 GQA attention layers (32 query and 2
key-value heads of 128).  d_inner is heads x head dim (4096), as NemotronH's
mixer computes it, not ``expand * d_model``.

The oracle decomposes, scores and ranks it (``core/network.py``); the model
zoo does not build it, so it is not in ``ARCHS``.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="nemotron-3-nano-30b-a3b",
    family="hybrid",
    n_layers=52,
    d_model=2688,
    n_heads=32,
    n_kv_heads=2,
    d_ff=1856,
    vocab=131072,
    head_dim=128,
    mlp="relu2",
    rope_theta=10000.0,
    moe_experts=128,
    moe_top_k=6,
    moe_shared_d_ff=3712,
    ssm_state=128,
    ssm_headdim=64,
    ssm_expand=2,
    ssm_conv=4,
    ssm_chunk=128,
    ssm_groups=8,
    ssm_n_heads=64,
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
)
