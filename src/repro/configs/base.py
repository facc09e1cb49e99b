"""Unified model/run configuration.

One ``ModelConfig`` covers all six architecture families (dense / moe / ssm /
hybrid / audio / vlm); family-specific fields are ignored by the others.
``reduced()`` produces the CPU-smoke variant (<=2 layers, d_model<=512,
<=4 experts) required per assigned architecture.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    source: str                    # citation per the assignment table
    num_layers: int
    d_model: int
    vocab_size: int
    d_ff: int = 0
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0              # 0 => d_model // num_heads
    qkv_bias: bool = False
    qk_norm: bool = False
    sliding_window: int | None = None
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_capacity_factor: float = 1.25
    # --- DeepSeek-V3 expert layer (moe_router == "sigmoid"): sigmoid scores
    # with a selection bias, top-k weights normalised then scaled, shared
    # experts, a sequence-wise balance loss and dropless dispatch over the
    # experts this chip holds (models/moe.py); d_ff is one routed expert ---
    moe_router: str = "softmax"    # softmax (capacity path) | sigmoid
    num_shared_experts: int = 0
    routed_scaling: float = 1.0
    balance_alpha: float = 0.0
    experts_held: int = 0          # 0 => all num_experts
    experts_held_lo: int = 0       # the first held expert's index
    first_dense_layers: int = 0    # leading layers with a dense SwiGLU ...
    dense_d_ff: int = 0            # ... of this width
    # --- multi-head latent attention (kv_lora_rank > 0; models/attention.py)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- SSM (rwkv / mamba) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    # --- hybrid (zamba2): one shared attn+mlp block every N mamba blocks ---
    shared_attn_every: int = 0
    # --- enc-dec (seamless) ---
    encoder_layers: int = 0
    encoder_seq_divisor: int = 4   # encoder frames = seq_len // divisor
    # --- modality frontend stubs ---
    frontend: str | None = None    # None | "audio" | "vision"
    num_patches: int = 256         # vision prefix length
    # --- numerics / memory ---
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # checkpoint each block (jax.checkpoint); what it keeps for the backward
    # is not set here: launch.steps.jit_group_train_step saves the blocks'
    # projection and MLP matmuls where the compiled step fits the device's
    # memory, and recomputes the whole block elsewhere
    remat: bool = True
    loss_chunk: int = 512

    def __post_init__(self):
        if self.num_heads and self.head_dim == 0:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)

    # ------------------------------------------------------------------
    def with_(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """CPU-smoke variant: same family/wiring, tiny dims."""
        heads = min(self.num_heads, 4) if self.num_heads else 0
        kv = min(self.num_kv_heads, max(heads // 2, 1)) if heads else 0
        d_model = min(self.d_model, 256)
        hd = d_model // heads if heads else 0
        return self.with_(
            name=self.name + "-reduced",
            num_layers=min(self.num_layers, 2),
            d_model=d_model,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=hd,
            vocab_size=min(self.vocab_size, 512),
            num_experts=min(self.num_experts, 4),
            experts_per_token=min(self.experts_per_token, 2),
            experts_held=min(self.experts_held, 4),
            first_dense_layers=min(self.first_dense_layers, 1),
            dense_d_ff=min(self.dense_d_ff, 512),
            kv_lora_rank=min(self.kv_lora_rank, 32),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 16),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 8),
            v_head_dim=min(self.v_head_dim, 16),
            sliding_window=(min(self.sliding_window, 64)
                            if self.sliding_window else None),
            encoder_layers=min(self.encoder_layers, 2),
            shared_attn_every=(2 if self.shared_attn_every else 0),
            num_patches=min(self.num_patches, 16),
            ssm_chunk=16,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=min(self.ssm_head_dim, 32),
            dtype=jnp.float32,
            param_dtype=jnp.float32,
            remat=False,
            loss_chunk=0,
        )

    @property
    def deepseek_moe(self) -> bool:
        """The DeepSeek-V3 block: latent attention, leading dense layers,
        then the sigmoid-routed expert layer with shared experts."""
        return self.family == "moe" and self.moe_router == "sigmoid"

    def _deepseek_counts(self) -> tuple[int, int, int]:
        """(latent attention, routed expert, MoE layer less its routed
        experts and attention) parameters of one layer."""
        D, H, R = self.d_model, self.num_heads, self.kv_lora_rank
        nope, rope, v = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim)
        mla = (D * H * (nope + rope) + D * (R + rope) + R
               + R * H * (nope + v) + H * v * D)
        expert = 3 * D * self.d_ff
        rest = (3 * D * self.num_shared_experts * self.d_ff
                + D * self.num_experts + self.num_experts + 2 * D)
        return mla, expert, rest

    # approximate parameter counts (used by roofline MODEL_FLOPS)
    def param_count(self) -> int:
        D, F, V, L = self.d_model, self.d_ff, self.vocab_size, self.num_layers
        emb = V * D * (1 if self.tie_embeddings else 2)
        if self.deepseek_moe:         # exact: what models.model.init makes
            mla, expert, rest = self._deepseek_counts()
            held = self.experts_held or self.num_experts
            n_dense = self.first_dense_layers
            dense = mla + 3 * D * self.dense_d_ff + 2 * D
            moe = mla + held * expert + rest
            return emb + D + n_dense * dense + (L - n_dense) * moe
        if self.family in ("dense", "vlm"):
            attn = D * self.num_heads * self.head_dim * 2 \
                + D * self.num_kv_heads * self.head_dim * 2
            mlp = 3 * D * F
            return emb + L * (attn + mlp)
        if self.family == "moe":
            attn = D * self.num_heads * self.head_dim * 2 \
                + D * self.num_kv_heads * self.head_dim * 2
            moe = self.num_experts * 3 * D * F + D * self.num_experts
            return emb + L * (attn + moe)
        if self.family == "ssm":       # rwkv6
            tm = 5 * D * D + D * 64 + 64 * D    # r,k,v,g,o + decay lora
            cm = 2 * D * F // 1 if F else 0
            cm = D * F * 2 + D * D
            return emb + L * (tm + cm)
        if self.family == "hybrid":    # zamba2
            din = 2 * D
            mamba = D * (2 * din + 2 * self.ssm_state
                         + din // self.ssm_head_dim) + din * D
            n_shared = 1
            attn = D * self.num_heads * self.head_dim * 2 \
                + D * self.num_kv_heads * self.head_dim * 2 + 3 * D * F
            return emb + L * mamba + n_shared * attn
        if self.family == "audio":     # enc-dec
            attn = D * self.num_heads * self.head_dim * 2 \
                + D * self.num_kv_heads * self.head_dim * 2
            mlp = 3 * D * F
            enc = self.encoder_layers * (attn + mlp)
            dec = self.num_layers * (2 * attn + mlp)  # self + cross
            return emb + enc + dec
        raise ValueError(self.family)

    def active_param_count(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if self.family != "moe":
            return self.param_count()
        if self.deepseek_moe:         # the held experts a token is sent to
            _, expert, _ = self._deepseek_counts()
            held = self.experts_held or self.num_experts
            unused = held - self.experts_per_token * held / self.num_experts
            moe_layers = self.num_layers - self.first_dense_layers
            return round(self.param_count() - moe_layers * unused * expert)
        D, F, L = self.d_model, self.d_ff, self.num_layers
        attn = D * self.num_heads * self.head_dim * 2 \
            + D * self.num_kv_heads * self.head_dim * 2
        act_moe = self.experts_per_token * 3 * D * F + D * self.num_experts
        emb = self.vocab_size * D * (1 if self.tie_embeddings else 2)
        return emb + L * (attn + act_moe)


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # "train" | "prefill" | "decode"

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"
