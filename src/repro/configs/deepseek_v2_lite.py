"""deepseek-v2-lite [moe]: 27L d_model=2048 16H, multi-head latent attention
(kv_lora_rank 512, no q compression, qk_nope 128 + qk_rope 64, v 128), YaRN
rope (factor 40 over 4096), layer 0 a dense MLP of 10944, layers 1-26 MoE:
64 routed experts of 1408, 6 per token (softmax, greedy, gates not
renormalised), 2 shared experts (one MLP of 2816), vocab 102400, untied.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, head_dim=192,
    d_ff=10944, vocab_size=102400,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0, yarn_factor=40.0, yarn_original_max_pos=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    n_experts=64, top_k=6, moe_d_ff=1408, shared_expert_d_ff=2816,
    moe_flags=(True,), first_k_dense=1, norm_topk_prob=False,
    norm_eps=1e-6, tie_embeddings=False,
).validate()
