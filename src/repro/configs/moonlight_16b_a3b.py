"""moonlight-16b-a3b: DeepSeek-V3 layers (model_type ``deepseek_v3``):
latent attention (MLA, no query compression), one leading dense SwiGLU
layer, then 26 MoE layers of 64 routed experts (6 a token, sigmoid scores
chosen with a correction bias) beside 2 shared experts
[hf:moonshotai/Moonlight-16B-A3B, config.json]."""
from repro.config import ModelConfig, Family

CONFIG = ModelConfig(
    arch_id="moonlight-16b-a3b", family=Family.MOE,
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab_size=163840, rope_theta=5e4, norm_eps=1e-5,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64, top_k=6, router="sigmoid_bias", routed_scale=2.446,
    shared_d_ff=2 * 1408, n_dense_layers=1, dense_d_ff=11264,
)
