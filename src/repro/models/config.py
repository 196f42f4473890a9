"""Model/architecture configuration for the 10 assigned architectures and
the ones the oracle only estimates.

Every architecture is expressed as a single ``ModelConfig``; family-specific
fields are zero/empty when unused.  The full configs (exercised only via the
dry-run) live in ``repro/configs/<arch>.py``; smoke tests instantiate
``reduced()`` variants that run a real step on CPU.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

Family = Literal["dense", "moe", "ssm", "hybrid", "vlm", "audio"]

#: kinds of layer in ``ModelConfig.layer_pattern``: Mamba-2, experts, attention
LAYER_KINDS = ("M", "E", "*")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: Family
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    head_dim: int = 0  # 0 -> d_model // n_heads
    qkv_bias: bool = False
    #: "relu2" is a non-gated squared-ReLU MLP: two matrices, like "gelu"
    mlp: Literal["swiglu", "gelu", "relu2"] = "swiglu"
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = False

    # --- MoE ---
    moe_experts: int = 0
    moe_top_k: int = 0
    capacity_factor: float = 1.25
    #: width of the shared expert every token passes through (0 = none)
    moe_shared_d_ff: int = 0
    #: leading layers whose FFN is one dense MLP of ``moe_dense_d_ff`` and
    #: not experts (DeepSeek's ``first_k_dense_replace``)
    moe_dense_layers: int = 0
    moe_dense_d_ff: int = 0

    # --- multi-head latent attention (DeepSeek MLA): queries through a
    # ``q_lora_rank`` latent, keys and values through one cached latent of
    # ``kv_lora_rank`` beside a ``qk_rope_head_dim`` key shared by all heads.
    # kv_lora_rank 0 = the GQA attention of n_heads / n_kv_heads / head_dim ---
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- multi-token prediction: depth of the MTP module (training only) ---
    mtp_layers: int = 0

    # --- SSM (Mamba2) ---
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 128
    #: groups sharing one B and one C projection (Mamba-2 ``n_groups``)
    ssm_groups: int = 1
    #: Mamba heads given outright; then d_inner = heads x head dim (NemotronH)
    #: and not ``ssm_expand * d_model``.  0 = derived from ``ssm_expand``.
    ssm_n_heads: int = 0

    # --- hybrid (zamba2-style): one shared attention+MLP block applied
    # after every `attn_every` mamba blocks (weights shared across uses) ---
    attn_every: int = 0

    # --- hybrid by layer pattern (NemotronH-style): one character per layer,
    # "M" a Mamba-2 mixer, "E" a mixture of experts with no attention in
    # front of it, "*" a GQA attention layer.  Empty = the family's own body.
    layer_pattern: str = ""

    # --- encoder-decoder (whisper-style) ---
    n_encoder_layers: int = 0
    encoder_seq: int = 0  # fixed frame count from the (stubbed) conv frontend

    # --- VLM (qwen2-vl-style) ---
    mrope: bool = False
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    vision_tokens: int = 0  # precomputed patch embeddings from the stub frontend

    # --- execution knobs (perf levers; see EXPERIMENTS.md §Perf) ---
    attention_impl: Literal["xla_chunked", "xla_full", "flash_pallas"] = "xla_chunked"
    attention_block_q: int = 512
    attention_block_k: int = 1024
    remat: Literal["none", "full", "dots"] = "full"
    scan_layers: bool = True
    #: fully unroll inner chunk loops (attention KV blocks, SSD chunks) --
    #: used by the dry-run cost probes so XLA cost analysis sees every trip
    inner_unroll: bool = False

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads > 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.kv_lora_rank and not self.q_lora_rank:
            raise ValueError("latent attention (kv_lora_rank) needs q_lora_rank")
        if self.layer_pattern:
            unknown = set(self.layer_pattern) - set(LAYER_KINDS)
            if unknown or len(self.layer_pattern) != self.n_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r} must hold {self.n_layers} "
                    f"characters of {''.join(LAYER_KINDS)!r}"
                )

    # ---------------------------------------------------------- derived sizes
    @property
    def d_inner(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads * self.ssm_headdim
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        if self.ssm_n_heads:
            return self.ssm_n_heads
        return self.d_inner // self.ssm_headdim if self.ssm_state else 0

    @property
    def mla(self) -> bool:
        """Multi-head latent attention in place of GQA."""
        return self.kv_lora_rank > 0

    @property
    def mlp_mats(self) -> int:
        """Matrices of one MLP or expert: three gated, two otherwise."""
        return 3 if self.mlp == "swiglu" else 2

    def layer_counts(self) -> dict[str, int]:
        """Layers of each kind of ``layer_pattern``, in order of first use."""
        return {k: self.layer_pattern.count(k) for k in dict.fromkeys(self.layer_pattern)}

    @property
    def is_encoder_decoder(self) -> bool:
        return self.n_encoder_layers > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic sequence mixing -> long_500k applies."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Total parameters (for 6ND model-FLOPs accounting)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        hd = self.head_dim
        n_q = self.n_heads * hd
        n_kv = self.n_kv_heads * hd
        attn = d * n_q + 2 * d * n_kv + n_q * d
        if self.qkv_bias:
            attn += n_q + 2 * n_kv
        if self.mla:
            h, rope = self.n_heads, self.qk_rope_head_dim
            q, kv = self.q_lora_rank, self.kv_lora_rank
            attn = (d * q + q * h * (self.qk_nope_head_dim + rope) + d * (kv + rope)
                    + kv * h * (self.qk_nope_head_dim + self.v_head_dim)
                    + h * self.v_head_dim * d + q + kv)  # + the two latents' norms
        mlp = d * f * self.mlp_mats
        moe_mlp = self.mlp_mats * d * f * self.moe_experts + d * self.moe_experts
        ssm = 0
        if self.ssm_state:
            di, g, n, h = self.d_inner, self.ssm_groups, self.ssm_state, self.ssm_heads
            proj_out = 2 * di + 2 * g * n + h
            ssm = d * proj_out + self.ssm_conv * (di + 2 * g * n) + 3 * h + di + di * d
        emb = v * d * (1 if self.tie_embeddings else 2)
        n = emb + 2 * d  # final norm(s)
        per_layer_norms = 2 * d
        if self.layer_pattern:
            kinds = self.layer_counts()
            shared = self.mlp_mats * d * self.moe_shared_d_ff
            n += kinds.get("M", 0) * (ssm + d)
            n += kinds.get("E", 0) * (moe_mlp + shared + d)
            n += kinds.get("*", 0) * (attn + d)
        elif self.family == "moe":
            shared = self.mlp_mats * d * self.moe_shared_d_ff
            n_moe = self.n_layers - self.moe_dense_layers
            n += self.n_layers * (attn + per_layer_norms) + n_moe * (moe_mlp + shared)
            n += self.moe_dense_layers * self.mlp_mats * d * self.moe_dense_d_ff
            # MTP: the concat projection, one more layer and its three norms;
            # the embedding and the LM head are shared with the main model
            n += self.mtp_layers * (2 * d * d + attn + moe_mlp + shared + per_layer_norms + 3 * d)
        elif self.family == "ssm":
            n += self.n_layers * (ssm + d)
        elif self.family == "hybrid":
            n_shared_uses = self.n_layers // max(1, self.attn_every)
            n += self.n_layers * (ssm + d) + (attn + mlp + per_layer_norms)
            del n_shared_uses  # weights are shared; count once
        elif self.is_encoder_decoder:
            cross = d * n_q + 2 * d * n_kv + n_q * d
            n += self.n_encoder_layers * (attn + mlp + per_layer_norms)
            n += self.n_layers * (attn + cross + mlp + 3 * d)
            n += self.encoder_seq * 0
        else:
            n += self.n_layers * (attn + mlp + per_layer_norms)
        return int(n)

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: top-k experts only)."""
        if not self.moe_experts:
            return self.param_count()
        if self.layer_pattern:
            moe_layers = self.layer_counts().get("E", 0)
        else:
            moe_layers = self.n_layers - self.moe_dense_layers + self.mtp_layers
        d, f = self.d_model, self.d_ff
        dense_moe = self.mlp_mats * d * f * self.moe_experts
        active_moe = self.mlp_mats * d * f * self.moe_top_k
        return int(self.param_count() - moe_layers * (dense_moe - active_moe))


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: Literal["train", "prefill", "decode"]


SHAPES: dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: InputShape) -> bool:
    """long_500k only for sub-quadratic archs (see DESIGN.md §Arch-applicability)."""
    if shape.name == "long_500k":
        return cfg.supports_long_context
    return True


def reduced(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family variant for CPU smoke tests; a layer pattern keeps
    one layer of each of its kinds, in order of first use."""
    pattern = "".join(dict.fromkeys(cfg.layer_pattern))
    return dataclasses.replace(
        cfg,
        n_layers=len(pattern) if pattern else min(
            cfg.n_layers, 2 * max(1, cfg.attn_every) if cfg.attn_every else 2),
        layer_pattern=pattern,
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads < cfg.n_heads else 4,
        head_dim=32,
        d_ff=256,
        vocab=512,
        moe_experts=min(cfg.moe_experts, 8) if cfg.moe_experts else 0,
        moe_top_k=min(cfg.moe_top_k, 2) if cfg.moe_top_k else 0,
        moe_shared_d_ff=256 if cfg.moe_shared_d_ff else 0,
        moe_dense_layers=min(cfg.moe_dense_layers, 1),
        moe_dense_d_ff=512 if cfg.moe_dense_d_ff else 0,
        q_lora_rank=32 if cfg.q_lora_rank else 0,
        kv_lora_rank=16 if cfg.kv_lora_rank else 0,
        qk_nope_head_dim=16 if cfg.qk_nope_head_dim else 0,
        qk_rope_head_dim=8 if cfg.qk_rope_head_dim else 0,
        v_head_dim=16 if cfg.v_head_dim else 0,
        ssm_state=min(cfg.ssm_state, 16) if cfg.ssm_state else 0,
        ssm_headdim=32 if cfg.ssm_state else cfg.ssm_headdim,
        ssm_groups=min(cfg.ssm_groups, 2),
        ssm_n_heads=4 if cfg.ssm_n_heads else 0,
        mrope_sections=(4, 6, 6) if cfg.mrope else cfg.mrope_sections,
        n_encoder_layers=min(cfg.n_encoder_layers, 2),
        encoder_seq=min(cfg.encoder_seq, 64) if cfg.encoder_seq else 0,
        vision_tokens=min(cfg.vision_tokens, 16) if cfg.vision_tokens else 0,
        attn_every=min(cfg.attn_every, 2) if cfg.attn_every else 0,
        attention_block_q=64,
        attention_block_k=64,
        remat="none",
    )
