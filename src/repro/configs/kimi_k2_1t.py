"""Kimi K2 1T-A32B [arXiv:2501.kimi2] — trillion-parameter MoE, 384 experts
top-8 (paper-table scale; the stress test for sharded GMoM).

Kimi K2 is DeepSeek-V3's architecture; here its latent attention stands as
GQA with 8 KV heads and its router as softmax with capacity.  The MLA path
and the DeepSeek-V3 expert layer (``configs/moonlight_16b_a3b.py``) can
take their place once a sharded share of this model is sized."""

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    source="arXiv:2501.kimi2",
    num_layers=61,
    d_model=7168,
    num_heads=64,
    num_kv_heads=8,
    d_ff=2048,              # per-expert hidden
    vocab_size=163840,
    num_experts=384,
    experts_per_token=8,
    rope_theta=1e6,
    moe_capacity_factor=1.25,
)
