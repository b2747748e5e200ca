"""Moonlight-16B-A3B — DeepSeek-V3 block at 2048 wide
[hf:moonshotai/Moonlight-16B-A3B, config.json]: multi-head latent
attention (kv_lora_rank 512, q·k width 128 + 64 rotary, v width 128), one
dense first layer (d_ff 11,264), then 64 routed experts of width 1408,
top-6, 2 shared, with the sigmoid noaux_tc router (routed scaling 2.446,
normalised top-k). The bias rate gamma 0.001 and the sequence-wise balance
weight alpha 1e-4 are not in the config: they are DeepSeek-V3's
(arXiv:2412.19437 §2.1.2).
"""
from repro.config import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11_264,             # the first (dense) layer's FFN width
    vocab_size=163_840,
    head_dim=192,            # q·k width: qk_nope 128 + qk_rope 64
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  d_expert=1408, router="sigmoid", routed_scaling=2.446,
                  router_aux_weight=1e-4, bias_update_rate=1e-3),
    first_dense=1,
    rope_theta=50_000.0,
    norm_eps=1e-5,
)
