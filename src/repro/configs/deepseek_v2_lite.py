"""DeepSeek-V2-Lite — 15.7B total, 2.4B active: latent attention (MLA) with
YaRN rope, one leading dense layer, then 26 MoE layers of 64 routed experts
(top-6, softmax scores, no top-k renormalization) and 2 shared experts.
[hf:deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json, arXiv:2405.04434]"""
from repro.configs.base import ArchConfig, ModelConfig, YarnRope

CONFIG = ArchConfig(
    model=ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        num_layers=27,
        first_k_dense=1,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,  # the dense layer's width
        vocab_size=102400,
        activation="swiglu",
        rope_theta=10000.0,
        rope_yarn=YarnRope(factor=40.0, original_max_position_embeddings=4096,
                           beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                           mscale_all_dim=0.707),
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        num_experts=64,
        experts_per_token=6,
        num_shared_experts=2,
        moe_d_ff=1408,
        experts_held=(0, 64),
        router_aux_coef=0.001,
        param_dtype="float32",
        compute_dtype="float32",
        matmul_dtype="bfloat16",
    ),
    source="[hf:deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]",
    notes="Published dtype bf16: parameters are kept in float32 here (the "
          "FL update is their difference) and every matmul of the model "
          "takes bf16 operands with float32 accumulation; the routed "
          "experts' grouped products also return bf16 (their (T*k, .) "
          "rows are the step's largest buffers). The balance "
          "loss's alpha (0.001) is not in the config: assumed. The expert "
          "layer holds all 64 experts; a deployment that divides each "
          "layer over chips sets experts_held to the chip's range.",
    skip_shapes=("prefill_32k", "decode_32k", "long_500k"),
    fl_mode="distributed",
)
