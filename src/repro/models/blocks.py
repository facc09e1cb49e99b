"""Per-family transformer blocks and their scanned-stack drivers.

Every stack uses ``jax.lax.scan`` over the layer axis (params carry a leading
L dim, initialized with vmap) so the lowered HLO is O(1) in depth — the
512-device dry-run of the 80-layer configs depends on this.  ``cfg.remat``
wraps the block body in ``jax.checkpoint`` (activation rematerialization).
What the checkpoint keeps is the train step's choice, not the config's:
``launch.steps.jit_group_train_step`` compiles the step with ``SAVE_DOTS``
(each block's projection and MLP matmul outputs kept for the backward; the
attention core, norms, rotary and activations recomputed) and keeps it
where the compiled peak fits the device's memory, else compiles full remat
(every value recomputed).  The step traces its stacks inside
``remat_policy(...)``; outside one, ``maybe_remat`` is full remat.
"""

from __future__ import annotations

import contextlib
import contextvars

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, layers, mamba, moe, rwkv


# ---------------------------------------------------------------------------
# spec builders

def attn_spec(cfg: ModelConfig, *, causal=True, cross=False,
              sliding_window="cfg") -> attention.AttentionSpec:
    return attention.AttentionSpec(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
        qkv_bias=cfg.qkv_bias,
        qk_norm=cfg.qk_norm,
        causal=causal,
        sliding_window=(cfg.sliding_window if sliding_window == "cfg"
                        else sliding_window),
        rope_theta=cfg.rope_theta,
        cross=cross,
    )


def moe_spec(cfg: ModelConfig) -> moe.MoESpec:
    return moe.MoESpec(
        d_model=cfg.d_model, d_ff=cfg.d_ff,
        num_experts=cfg.num_experts,
        experts_per_token=cfg.experts_per_token,
        capacity_factor=cfg.moe_capacity_factor)


def mla_spec(cfg: ModelConfig) -> attention.MLASpec:
    return attention.MLASpec(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim,
        v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta)


def deepseek_moe_spec(cfg: ModelConfig) -> moe.DeepSeekMoESpec:
    return moe.DeepSeekMoESpec(
        d_model=cfg.d_model, d_ff=cfg.d_ff, num_experts=cfg.num_experts,
        experts_per_token=cfg.experts_per_token,
        num_shared=cfg.num_shared_experts,
        routed_scaling=cfg.routed_scaling,
        balance_alpha=cfg.balance_alpha,
        held=cfg.experts_held or cfg.num_experts,
        held_lo=cfg.experts_held_lo)


def rwkv_spec(cfg: ModelConfig) -> rwkv.RWKVSpec:
    return rwkv.RWKVSpec(d_model=cfg.d_model, d_ff=cfg.d_ff,
                         head_dim=cfg.ssm_head_dim)


def mamba_spec(cfg: ModelConfig) -> mamba.MambaSpec:
    return mamba.MambaSpec(d_model=cfg.d_model, d_state=cfg.ssm_state,
                           head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk)


# ---------------------------------------------------------------------------
# decoder block (dense / moe / vlm) — pre-norm GQA + (SwiGLU | MoE)

def init_decoder_block(key, cfg: ModelConfig, *, cross: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "ln_attn": layers.rmsnorm_init(cfg.d_model, dtype=cfg.param_dtype),
        "attn": attention.init(ks[0], attn_spec(cfg), dtype=cfg.param_dtype),
        "ln_mlp": layers.rmsnorm_init(cfg.d_model, dtype=cfg.param_dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe.init(ks[1], moe_spec(cfg), dtype=cfg.param_dtype)
    else:
        p["mlp"] = layers.swiglu_init(ks[1], cfg.d_model, cfg.d_ff,
                                      dtype=cfg.param_dtype)
    if cross:
        p["ln_cross"] = layers.rmsnorm_init(cfg.d_model,
                                            dtype=cfg.param_dtype)
        p["cross"] = attention.init(
            ks[2], attn_spec(cfg, cross=True), dtype=cfg.param_dtype)
    return p


def decoder_block(p, cfg: ModelConfig, x, *, memory=None, positions=None):
    """(x, aux) -> (x, aux).  Full-sequence (train/prefill)."""
    h = attention.apply(p["attn"], attn_spec(cfg),
                        layers.rmsnorm(p["ln_attn"], x, eps=cfg.norm_eps),
                        positions=positions)
    x = x + h
    if "cross" in p:
        h = attention.apply(p["cross"], attn_spec(cfg, cross=True),
                            layers.rmsnorm(p["ln_cross"], x,
                                           eps=cfg.norm_eps),
                            memory=memory, positions=positions)
        x = x + h
    normed = layers.rmsnorm(p["ln_mlp"], x, eps=cfg.norm_eps)
    if cfg.family == "moe":
        h, aux = moe.apply(p["moe"], moe_spec(cfg), normed)
    else:
        h, aux = layers.swiglu(p["mlp"], normed), jnp.zeros((), jnp.float32)
    return x + h, aux


def decoder_block_decode(p, cfg: ModelConfig, x, cache, position, *,
                         memory=None):
    """One-token decode through a decoder block. cache: attention cache dict
    (plus nothing else — MoE/MLP are stateless)."""
    h, new_cache = attention.decode_step(
        p["attn"], attn_spec(cfg),
        layers.rmsnorm(p["ln_attn"], x, eps=cfg.norm_eps),
        cache["self"], position)
    x = x + h
    if "cross" in p:
        h, _ = attention.decode_step(
            p["cross"], attn_spec(cfg, cross=True),
            layers.rmsnorm(p["ln_cross"], x, eps=cfg.norm_eps),
            None, position, memory=memory)
        x = x + h
    normed = layers.rmsnorm(p["ln_mlp"], x, eps=cfg.norm_eps)
    if cfg.family == "moe":
        h, _ = moe.apply(p["moe"], moe_spec(cfg), normed)
    else:
        h = layers.swiglu(p["mlp"], normed)
    return x + h, {"self": new_cache}


# DeepSeek-V3 block: pre-norm latent attention + (dense SwiGLU | experts)

def init_deepseek_block(key, cfg: ModelConfig, *, dense: bool):
    k_attn, k_mlp = jax.random.split(key)
    p = {
        "ln_attn": layers.rmsnorm_init(cfg.d_model, dtype=cfg.param_dtype),
        "attn": attention.mla_init(k_attn, mla_spec(cfg),
                                   dtype=cfg.param_dtype),
        "ln_mlp": layers.rmsnorm_init(cfg.d_model, dtype=cfg.param_dtype),
    }
    if dense:
        p["mlp"] = layers.swiglu_init(k_mlp, cfg.d_model, cfg.dense_d_ff,
                                      dtype=cfg.param_dtype)
    else:
        p["moe"] = moe.deepseek_init(k_mlp, deepseek_moe_spec(cfg),
                                     dtype=cfg.param_dtype)
    return p


def deepseek_block(p, cfg: ModelConfig, x):
    """x -> (x, balance loss, loads of the held experts (held,) int32).
    A leading dense layer (``"mlp"`` in p) returns a loss of 0 and no
    loads."""
    x = x + attention.mla_apply(
        p["attn"], mla_spec(cfg),
        layers.rmsnorm(p["ln_attn"], x, eps=cfg.norm_eps))
    normed = layers.rmsnorm(p["ln_mlp"], x, eps=cfg.norm_eps)
    if "mlp" in p:
        return (x + layers.swiglu(p["mlp"], normed),
                jnp.zeros((), jnp.float32), None)
    h, balance, loads = moe.deepseek_apply(p["moe"], deepseek_moe_spec(cfg),
                                           normed)
    return x + h, balance, loads


# encoder block (audio family): bidirectional self-attn + GELU MLP

def init_encoder_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "ln_attn": layers.layernorm_init(cfg.d_model, dtype=cfg.param_dtype),
        "attn": attention.init(
            ks[0], attn_spec(cfg, causal=False, sliding_window=None),
            dtype=cfg.param_dtype),
        "ln_mlp": layers.layernorm_init(cfg.d_model, dtype=cfg.param_dtype),
        "mlp": layers.gelu_mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                    dtype=cfg.param_dtype),
    }


def encoder_block(p, cfg: ModelConfig, x):
    spec = attn_spec(cfg, causal=False, sliding_window=None)
    x = x + attention.apply(
        p["attn"], spec, layers.layernorm(p["ln_attn"], x, eps=cfg.norm_eps))
    x = x + layers.gelu_mlp(
        p["mlp"], layers.layernorm(p["ln_mlp"], x, eps=cfg.norm_eps))
    return x


# rwkv block

def init_rwkv_block(key, cfg: ModelConfig):
    p = rwkv.init(key, rwkv_spec(cfg), dtype=cfg.param_dtype)
    p["ln_tm"] = layers.layernorm_init(cfg.d_model, dtype=cfg.param_dtype)
    p["ln_cm"] = layers.layernorm_init(cfg.d_model, dtype=cfg.param_dtype)
    return p


def rwkv_block(p, cfg: ModelConfig, x, *, state=None):
    """state = (prev_tm, wkv, prev_cm) or None (train)."""
    spec = rwkv_spec(cfg)
    prev_tm = wkv_state = prev_cm = None
    if state is not None:
        prev_tm, wkv_state, prev_cm = state
    h, (new_prev_tm, new_wkv) = rwkv.time_mix(
        p["time_mix"], spec, layers.layernorm(p["ln_tm"], x,
                                              eps=cfg.norm_eps),
        prev_token=prev_tm, wkv_state=wkv_state)
    x = x + h
    h, new_prev_cm = rwkv.channel_mix(
        p["channel_mix"], spec, layers.layernorm(p["ln_cm"], x,
                                                 eps=cfg.norm_eps),
        prev_token=prev_cm)
    x = x + h
    return x, (new_prev_tm, new_wkv, new_prev_cm)


# mamba block (zamba2)

def init_mamba_block(key, cfg: ModelConfig):
    p = mamba.init(key, mamba_spec(cfg), dtype=cfg.param_dtype)
    p["ln"] = layers.rmsnorm_init(cfg.d_model, dtype=cfg.param_dtype)
    return p


def mamba_block(p, cfg: ModelConfig, x, *, state=None):
    conv_state = ssm_state = None
    if state is not None:
        conv_state, ssm_state = state
    h, new_state = mamba.apply(
        p, mamba_spec(cfg), layers.rmsnorm(p["ln"], x, eps=cfg.norm_eps),
        conv_state=conv_state, ssm_state=ssm_state)
    return x + h, new_state


def mamba_block_decode(p, cfg: ModelConfig, x, state):
    conv_state, ssm_state = state
    h, new_state = mamba.decode_step(
        p, mamba_spec(cfg), layers.rmsnorm(p["ln"], x, eps=cfg.norm_eps),
        conv_state, ssm_state)
    return x + h, new_state


# ---------------------------------------------------------------------------
# stack helpers

def init_stacked(init_fn, key, num: int):
    """vmap an init over ``num`` split keys -> params with leading L dim."""
    keys = jax.random.split(key, num)
    return jax.vmap(init_fn)(keys)


# matmuls whose operands share no batch dimension: the q/k/v/o, latent and
# MLP projections, not the attention core's score and value products
SAVE_DOTS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable

_REMAT_POLICY = contextvars.ContextVar("remat_policy", default=None)


@contextlib.contextmanager
def remat_policy(policy):
    """Trace ``maybe_remat``'s checkpoints inside with ``policy`` (None:
    full remat)."""
    token = _REMAT_POLICY.set(policy)
    try:
        yield
    finally:
        _REMAT_POLICY.reset(token)


def maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat:
        return fn
    return jax.checkpoint(fn, policy=_REMAT_POLICY.get())
