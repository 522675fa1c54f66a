"""Moonlight-16B-A3B (model_type deepseek_v3): latent attention, one
leading dense layer, then layers of 64 routed experts (6 per token,
sigmoid scores, normalised gates times 2.446) plus 2 shared experts."""
from .base import ArchConfig
CONFIG = ArchConfig(
    name="moonlight-16b-a3b", family="moe", n_layers=27, d_model=2048,
    n_heads=16, n_kv_heads=16, d_ff=11264, vocab=163840,
    n_experts=64, top_k=6, d_expert=1408, n_shared_experts=2, first_dense=1,
    routed_scale=2.446,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rope_theta=50000.0, norm_eps=1e-5,
    source="hf:moonshotai/Moonlight-16B-A3B config.json")
