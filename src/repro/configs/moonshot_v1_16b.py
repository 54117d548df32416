"""moonshot-v1-16b-a3b [hf:moonshotai/Moonlight-16B-A3B; config.json, deepseek_v3].

Moonlight-16B-A3B: 27 layers of width 2048, vocab 163,840, untied.
* Attention: multi-head latent attention, 16 heads; no q compression; keys
  and values from a 512-wide latent (RMS-normed), q.k 128 + 64 rotary wide
  (the rotary key shared by all heads), values 128 wide; rope_theta 50,000.
* Layer 0: a dense SwiGLU MLP of width 11,264 (first_k_dense_replace 1).
* Layers 1-26: 64 routed SwiGLU experts of width 1,408, top 6, and 2 shared
  experts (one MLP of width 2,816); sigmoid router with a correction bias
  (noaux_tc, one group), gates normalized and scaled by 2.446, and a
  sequence-wise balance loss.  Its weight (1e-4) and the bias's step (1e-3)
  are DeepSeek-V3's (arXiv:2412.19437); the config gives neither.
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonshot-v1-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=1408,
    vocab=163840, n_experts=64, top_k=6, router="sigmoid", routed_scale=2.446,
    moe_aux_weight=1e-4, bias_rate=1e-3, shared_d_ff=2816,
    n_dense_layers=1, dense_d_ff=11264,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=50_000.0,
    notes="MLA; 64e top-6 sigmoid router + 2 shared; full attention (skip long_500k)",
)

SMOKE = ArchConfig(
    name="moonshot-v1-16b-a3b-smoke", family="moe",
    n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32, vocab=512,
    n_experts=16, experts_held=8, top_k=6, router="sigmoid", routed_scale=2.446,
    moe_aux_weight=1e-4, bias_rate=1e-3, shared_d_ff=64,
    n_dense_layers=1, dense_d_ff=128,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=50_000.0,
)
