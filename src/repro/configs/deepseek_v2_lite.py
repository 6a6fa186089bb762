"""deepseek-v2-lite [moe] -- MLA without q-LoRA, YaRN rope, a dense first
layer, then 2 shared + 64 routed experts (top-6 softmax, not renormalised).
[hf:deepseek-ai/DeepSeek-V2-Lite]

27L d_model=2048 16H vocab=102400, untied head, RMSNorm eps 1e-6. MLA:
q_lora_rank null, kv_lora_rank 512, qk_nope 128, qk_rope 64, v_head 128,
rope_theta 10000 with YaRN (factor 40, beta_fast 32, beta_slow 1,
original 4096, mscale = mscale_all_dim = 0.707). first_k_dense_replace 1:
layer 0 is a SwiGLU of 10944; layers 1-26 hold 64 routed experts of 1408
(top-6, softmax scores, norm_topk_prob false, routed_scaling_factor 1)
and 2 shared experts of 1408 (official config).

``CONFIG`` holds every expert. A deployment that spreads the experts over
chips sets ``moe.held_start`` / ``moe.held_count`` to one chip's share.
"""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=192,  # qk_nope + qk_rope
    d_ff=10944,  # the dense first layer
    vocab_size=102400,
    norm="rmsnorm",
    norm_eps=1e-6,
    rope_theta=10000.0,
    rope_scaling=YaRNConfig(
        factor=40.0, original_max_position=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707,
    ),
    first_k_dense=1,
    mla=MLAConfig(
        q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
        v_head_dim=128, rope_interleave=True,
    ),
    moe=MoEConfig(
        n_experts=64, top_k=6, d_ff_expert=1408, n_shared_experts=2,
        norm_topk_prob=False, routed_scaling_factor=1.0,
    ),
    tie_embeddings=False,
)

# every mechanism of CONFIG at CPU size: a dense first layer, shared
# experts, half of the routed experts held, no q-LoRA, YaRN
TINY = ModelConfig(
    name="deepseek-v2-lite-tiny",
    family="moe",
    n_layers=3,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_head=24,
    d_ff=96,
    vocab_size=256,
    norm="rmsnorm",
    norm_eps=1e-6,
    rope_theta=10000.0,
    rope_scaling=YaRNConfig(
        factor=40.0, original_max_position=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707,
    ),
    first_k_dense=1,
    mla=MLAConfig(
        q_lora_rank=None, kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, rope_interleave=True,
    ),
    moe=MoEConfig(
        n_experts=8, top_k=3, d_ff_expert=16, n_shared_experts=2,
        norm_topk_prob=False, routed_scaling_factor=1.0, held_start=0,
        held_count=4,
    ),
    tie_embeddings=False,
    dtype="float32",
)
