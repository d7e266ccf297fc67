"""moonlight-16b-a3b [moe]: 27L d_model=2048 16H, MLA without query
compression, layer 0 dense (d_ff=11264), 26 MoE layers of 64 routed experts
(width 1408, top-6, sigmoid scores chosen with a correction bias, weights
renormalised and scaled by 2.446) plus 2 shared experts, vocab=163840
untied [hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3].

MLA: kv_lora 512, qk_nope 128, qk_rope 64 (rope dims de-interleaved as the
published DeepSeek-V3 code does), v_head 128, RoPE theta 50000.  The two
shared experts are one SwiGLU MLP of width 2 x 1408 on the same input.
"""

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe", num_layers=27, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=11264, vocab_size=163840,
    attention="mla", rope_theta=50000.0, norm_eps=1e-5, first_k_dense=1,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, rope_interleave=True),
    moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                  capacity_factor=0.0, dense_residual_d_ff=2 * 1408,
                  scoring="sigmoid", score_bias=True,
                  routed_scaling=2.446),
)

SMOKE = ModelConfig(
    name="moonlight-16b-a3b-smoke", family="moe", num_layers=3, d_model=64,
    num_heads=4, num_kv_heads=4, d_ff=128, vocab_size=128,
    attention="mla", rope_theta=50000.0, norm_eps=1e-5, first_k_dense=1,
    mla=MLAConfig(q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=8, v_head_dim=8, rope_interleave=True),
    moe=MoEConfig(num_experts=16, top_k=4, expert_d_ff=32,
                  capacity_factor=0.0, dense_residual_d_ff=2 * 32,
                  scoring="sigmoid", score_bias=True,
                  routed_scaling=2.446),
)
