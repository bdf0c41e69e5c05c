"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, MLA (kv_lora 512, qk 128
nope + 64 rope, v 128, no query low-rank), YaRN rope (factor 40 over 4096),
one dense SwiGLU layer (d_ff 10944), then 26 MoE layers: 64 routed experts
of width 1408, softmax top-6 without renormalisation, 2 shared experts.
vocab=102400, untied head. 15.7B parameters, 2.4B active.
[hf:deepseek-ai/DeepSeek-V2-Lite]"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, head_dim=128,
    norm_eps=1e-6, rope_theta=1e4,
    attention_type="mla",
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    leading_dense_layers=1,
    block_pattern=(("attn", "moe"),),
    moe=MoEConfig(num_experts=64, router_experts=64, top_k=6, d_expert=1408,
                  num_shared=2, norm_topk_prob=False),
)

SMOKE = CONFIG.replace(
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, head_dim=16,
    d_ff=128, vocab_size=512, loss_chunk=0,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(num_experts=8, router_experts=8, top_k=2, d_expert=32,
                  num_shared=2, norm_topk_prob=False),
)
