"""kimi-k2-1t-a32b [moe] — 61L d_model=7168 64H (GQA kv=8) d_ff=2048
vocab=163840, MoE 384 experts top-8 — trillion-param MoE (paper-table).
Sharding: experts over 'model' (EP) + expert d_model over 'data' (FSDP) +
embeddings FSDP — 1T bf16 params => ~8 GB/chip at 256-way weight sharding
(arithmetic, not measured). [arXiv:2501.kimi2; unverified]
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=64,
    num_kv_heads=8,
    head_dim=128,
    d_ff=2048,                     # per-expert
    vocab_size=163840,
    num_experts=384,
    experts_per_token=8,
    capacity_factor=1.25,
    hidden_act="silu",
    mlp_gated=True,
    norm="rmsnorm",
    rope_theta=50_000.0,
    remat="full",
    sharding_overrides={"expert_in": "data", "embed_fsdp": "data"},
)


def reduced() -> ModelConfig:
    return CONFIG.replace(num_layers=2, d_model=64, num_heads=4,
                          num_kv_heads=2, head_dim=16, d_ff=32,
                          vocab_size=256, num_experts=4,
                          experts_per_token=2, remat="none",
                          sharding_overrides={})
