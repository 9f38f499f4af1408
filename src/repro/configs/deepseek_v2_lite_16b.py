"""deepseek-v2-lite-16b [moe] — MLA kv_lora=512, 2 shared + 64 routed
top-6 [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json].

27L d_model=2048 16H, per-expert d_ff=1408, vocab=102400 with an untied
head, first layer dense (d_ff=10944); no q LoRA; YaRN rope (factor 40
over 4096 positions, mscale 0.707); softmax gate, greedy top-6, not
renormalised; sequence-wise balance loss (alpha 0.001).
"""
from repro.models.config import ModelConfig

FULL = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    vocab=102400,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    use_mla=True,
    kv_lora=512,
    q_lora=0,
    rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    d_ff=10944,
    n_experts=64,
    n_shared_experts=2,
    moe_top_k=6,
    norm_topk_prob=False,
    d_ff_expert=1408,
    first_dense_layers=1,
    router_aux_coef=0.001,
    tie_embeddings=False,
    dtype="bfloat16",
)

SMOKE = FULL.replace(
    name="deepseek-lite-smoke",
    n_layers=2,
    d_model=128,
    vocab=512,
    n_heads=4,
    n_kv_heads=4,
    head_dim=32,
    kv_lora=64,
    q_lora=0,
    rope_head_dim=16,
    v_head_dim=32,
    d_ff=256,
    n_experts=4,
    n_shared_experts=1,
    moe_top_k=2,
    d_ff_expert=64,
    dtype="float32",
)
