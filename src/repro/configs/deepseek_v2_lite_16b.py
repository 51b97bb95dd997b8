"""deepseek-v2-lite-16b [moe] — MLA + 2 shared / 64 routed top-6 experts.

[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]
27 layers, d_model 2048, 16 MLA heads (no q LoRA, kv_lora_rank 512,
nope/rope/v head dims 128/64/128), vocabulary 102,400, untied embeddings.
Layer 0 keeps a dense SwiGLU FFN of width 10,944 (first_k_dense_replace 1);
layers 1-26 are MoE layers: 64 routed experts of width 1,408, softmax
scoring, greedy top-6 with the raw probabilities as weights
(norm_topk_prob false, routed_scaling_factor 1), and 2 shared experts.
YaRN rope: factor 40 over 4,096 original positions, beta_fast 32,
beta_slow 1, mscale = mscale_all_dim = 0.707, rope_theta 10,000.
Embeddings are not scaled by sqrt(d_model).
"""

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,  # the dense layer 0's FFN
    vocab_size=102400,
    attn_kind="mla",
    kv_lora_rank=512,
    q_lora_rank=0,
    rope_head_dim=64,
    nope_head_dim=128,
    v_head_dim=128,
    rope_theta=10000.0,
    rope_factor=40.0,
    rope_original_max_pos=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=0.707,
    rope_mscale_all_dim=0.707,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    moe_d_ff=1408,
    first_dense_layers=1,
    norm_topk_prob=False,
    tie_embeddings=False,
    scale_embeddings=False,
)
