"""DeepSeek-V2-Lite: multi-head latent attention (kv rank 512, no query
compression, 128 nope + 64 rope query/key dims, 128 value dims, YaRN x40),
a dense first layer, then 26 MoE layers of 64 routed experts (top-6,
softmax, not renormalized) plus 2 shared experts.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434 §2.1-2.2]"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10_944,
    vocab_size=102_400, pattern_unit=("mla",), norm_eps=1e-6,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, n_experts=64, topk=6, moe_d_ff=1408, n_shared_experts=2,
    first_k_dense=1, norm_topk_prob=False, routed_scaling=1.0,
    rope_theta=10_000.0, yarn_factor=40.0, yarn_original_max_pos=4096,
    yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
)

# same structure: a dense first layer, MoE layers holding a share of more
# experts than they hold, query/key heads wider than value heads, YaRN
SMOKE = CONFIG.replace(
    name="deepseek-v2-lite-smoke", n_layers=3, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, vocab_size=256, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, n_experts=16,
    topk=4, moe_d_ff=24, experts_held=4, expert_shard=1,
    yarn_original_max_pos=16, attn_q_chunk=8, attn_kv_chunk=8,
)
