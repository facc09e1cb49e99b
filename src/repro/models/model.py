"""Unified model: init / loss / forward / decode for all six families.

The model is selected by ``cfg.family``:

  dense, vlm   — scanned pre-norm GQA decoder (vlm prepends patch embeddings)
  moe          — same skeleton with MoE FFN + router aux loss; the
                 DeepSeek-V3 block (``cfg.deepseek_moe``): latent attention,
                 leading dense layers, then the sigmoid-routed expert stack
  ssm          — RWKV6 stack (token-shift states instead of KV cache)
  hybrid       — Zamba2: groups of Mamba2 blocks + one *shared* attn block
  audio        — Seamless-style encoder (stub frames) + cross-attn decoder

Batch formats (leaves may carry extra leading worker axes; these functions
see one worker's shard):

  train:   {"tokens": (B,T) i32, "labels": (B,T) i32}
           + vlm: {"patches": (B,P,D)}   + audio: {"frames": (B,Te,D)}
  decode:  tokens (B,1) i32, positions (B,) i32, state pytree
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, blocks, layers, mamba, rwkv


# ---------------------------------------------------------------------------
# init

def init(key, cfg: ModelConfig):
    k_embed, k_unembed, k_layers, k_extra = jax.random.split(key, 4)
    params = {
        "embed": layers.embed_init(k_embed, cfg.vocab_size, cfg.d_model,
                                   dtype=cfg.param_dtype),
        "ln_f": layers.rmsnorm_init(cfg.d_model, dtype=cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = layers.dense_init(
            k_unembed, cfg.d_model, cfg.vocab_size, dtype=cfg.param_dtype)

    if cfg.deepseek_moe:
        if cfg.first_dense_layers:
            params["dense_layers"] = blocks.init_stacked(
                lambda k: blocks.init_deepseek_block(k, cfg, dense=True),
                k_extra, cfg.first_dense_layers)
        params["layers"] = blocks.init_stacked(
            lambda k: blocks.init_deepseek_block(k, cfg, dense=False),
            k_layers, cfg.num_layers - cfg.first_dense_layers)
    elif cfg.family in ("dense", "vlm", "moe"):
        params["layers"] = blocks.init_stacked(
            lambda k: blocks.init_decoder_block(k, cfg), k_layers,
            cfg.num_layers)
    elif cfg.family == "ssm":
        params["layers"] = blocks.init_stacked(
            lambda k: blocks.init_rwkv_block(k, cfg), k_layers,
            cfg.num_layers)
    elif cfg.family == "hybrid":
        groups, per = _hybrid_shape(cfg)
        keys = jax.random.split(k_layers, groups)
        params["mamba"] = jax.vmap(
            lambda k: blocks.init_stacked(
                lambda kk: blocks.init_mamba_block(kk, cfg), k, per))(keys)
        params["shared"] = blocks.init_decoder_block(k_extra, cfg)
    elif cfg.family == "audio":
        params["layers"] = blocks.init_stacked(
            lambda k: blocks.init_decoder_block(k, cfg, cross=True),
            k_layers, cfg.num_layers)
        k_enc, _ = jax.random.split(k_extra)
        params["encoder"] = blocks.init_stacked(
            lambda k: blocks.init_encoder_block(k, cfg), k_enc,
            cfg.encoder_layers)
        params["enc_ln"] = layers.layernorm_init(cfg.d_model,
                                                 dtype=cfg.param_dtype)
    else:
        raise ValueError(f"unknown family {cfg.family!r}")
    return params


def _hybrid_shape(cfg: ModelConfig) -> tuple[int, int]:
    every = cfg.shared_attn_every or cfg.num_layers
    if cfg.num_layers % every != 0:
        raise ValueError("num_layers must be divisible by shared_attn_every")
    return cfg.num_layers // every, every


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)

def _embed(params, cfg: ModelConfig, tokens):
    return params["embed"][tokens].astype(cfg.dtype)


def _unembed_fn(params, cfg: ModelConfig):
    w = (params["embed"].T if cfg.tie_embeddings else params["unembed"])
    return lambda h: jnp.einsum("...d,dv->...v", h, w)


def _run_encoder(params, cfg: ModelConfig, frames):
    x = frames.astype(cfg.dtype)
    block = blocks.maybe_remat(
        lambda p, h: blocks.encoder_block(p, cfg, h), cfg)

    def body(h, p):
        return block(p, h), None

    x, _ = jax.lax.scan(body, x, params["encoder"])
    return layers.layernorm(params["enc_ln"], x, eps=cfg.norm_eps)


def _run_decoder_stack(params_stack, cfg: ModelConfig, x, *, memory=None):
    """Scanned decoder (dense/moe/vlm/audio).  Returns (hidden, aux)."""
    block = blocks.maybe_remat(
        lambda p, h: blocks.decoder_block(p, cfg, h, memory=memory), cfg)

    def body(carry, p):
        h, aux = carry
        h, a = block(p, h)
        return (h, aux + a), None

    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params_stack)
    return x, aux


def _run_deepseek_stack(params, cfg: ModelConfig, x):
    """The leading dense layers, then the expert layers, each stack
    scanned.  Returns (hidden, balance loss, loads (expert layers, held))."""
    block = blocks.maybe_remat(
        lambda p, h: blocks.deepseek_block(p, cfg, h), cfg)
    if "dense_layers" in params:
        x, _ = jax.lax.scan(lambda h, p: (block(p, h)[0], None), x,
                            params["dense_layers"])

    def body(carry, p):
        h, aux = carry
        h, a, loads = block(p, h)
        return (h, aux + a), loads

    (x, aux), loads = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    return x, aux, loads


def _run_rwkv_stack(params_stack, cfg: ModelConfig, x, *, states=None):
    block = blocks.maybe_remat(
        lambda p, h, s: blocks.rwkv_block(p, cfg, h, state=s), cfg)
    if states is None:
        def body(h, p):
            h, _ = block(p, h, None)
            return h, None
        x, _ = jax.lax.scan(body, x, params_stack)
        return x, None

    def body(h, ps):
        p, s = ps
        h, new_s = block(p, h, s)
        return h, new_s
    x, new_states = jax.lax.scan(body, x, (params_stack, states))
    return x, new_states


def _run_hybrid_stack(params, cfg: ModelConfig, x, *, states=None):
    """Zamba2: [shared attn block, `every` mamba blocks] × groups."""
    mamba_fn = blocks.maybe_remat(
        lambda p, h, s: blocks.mamba_block(p, cfg, h, state=s), cfg)
    shared_fn = blocks.maybe_remat(
        lambda h: blocks.decoder_block(params["shared"], cfg, h)[0], cfg)

    def inner(h, ps):
        p, s = ps
        h, new_s = mamba_fn(p, h, s)
        return h, new_s

    if states is None:
        def group(h, p_group):
            h = shared_fn(h)
            B = h.shape[0]
            spec = blocks.mamba_spec(cfg)
            per = jax.tree.leaves(p_group)[0].shape[0]
            conv0, ssm0 = mamba.init_states(spec, B, dtype=h.dtype)
            init_s = jax.tree.map(
                lambda s: jnp.broadcast_to(s[None], (per,) + s.shape),
                (conv0, ssm0))
            h, _ = jax.lax.scan(inner, h, (p_group, init_s))
            return h, None
        x, _ = jax.lax.scan(group, x, params["mamba"])
        return x, None
    raise NotImplementedError("full-seq hybrid with states: use decode path")


def forward(params, cfg: ModelConfig, batch):
    """Full-sequence hidden states (B, T, D) + aux loss."""
    tokens = batch["tokens"]
    x = _embed(params, cfg, tokens)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(cfg.dtype)
        x = jnp.concatenate([patches, x], axis=1)

    if cfg.deepseek_moe:
        h, aux, _ = _run_deepseek_stack(params, cfg, x)
    elif cfg.family in ("dense", "vlm", "moe"):
        h, aux = _run_decoder_stack(params["layers"], cfg, x)
    elif cfg.family == "ssm":
        h, _ = _run_rwkv_stack(params["layers"], cfg, x)
        aux = jnp.zeros((), jnp.float32)
    elif cfg.family == "hybrid":
        h, _ = _run_hybrid_stack(params, cfg, x)
        aux = jnp.zeros((), jnp.float32)
    elif cfg.family == "audio":
        memory = _run_encoder(params, cfg, batch["frames"])
        h, aux = _run_decoder_stack(params["layers"], cfg, x, memory=memory)
    else:
        raise ValueError(cfg.family)

    h = layers.rmsnorm(params["ln_f"], h, eps=cfg.norm_eps)
    if cfg.family == "vlm":
        h = h[:, batch["patches"].shape[1]:, :]   # text positions only
    return h, aux


def loss_fn(params, batch, cfg: ModelConfig):
    """Mean next-token cross entropy (+ MoE aux)."""
    h, aux = forward(params, cfg, batch)
    ce = layers.cross_entropy_loss(
        _unembed_fn(params, cfg), h, batch["labels"],
        vocab_chunk=cfg.loss_chunk)
    return ce + aux


def loss_and_stats(params, batch, cfg: ModelConfig):
    """``loss_fn`` of the DeepSeek-V3 block, and the router's counters:
    ``{"expert_loads": (expert layers, held) int32}``, the assignments each
    held expert took in each expert layer."""
    x = _embed(params, cfg, batch["tokens"])
    h, aux, loads = _run_deepseek_stack(params, cfg, x)
    h = layers.rmsnorm(params["ln_f"], h, eps=cfg.norm_eps)
    ce = layers.cross_entropy_loss(
        _unembed_fn(params, cfg), h, batch["labels"],
        vocab_chunk=cfg.loss_chunk)
    return ce + aux, {"expert_loads": loads}


def logits(params, cfg: ModelConfig, batch):
    """Full logits (small-scale tests only — O(B·T·V) memory)."""
    h, _ = forward(params, cfg, batch)
    return _unembed_fn(params, cfg)(h)


# ---------------------------------------------------------------------------
# decode

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    """State pytree for single-token decoding against a ``max_len`` context.

    For attention families this is the KV cache the decode_32k / long_500k
    shapes size against; for SSM/hybrid it is O(1) recurrent state."""
    if cfg.deepseek_moe:
        raise NotImplementedError("no decode path for latent attention")
    spec = blocks.attn_spec(cfg)
    if cfg.family in ("dense", "vlm", "moe"):
        cache = {"self": attention.init_cache(spec, batch, max_len,
                                              dtype=cfg.dtype)}
        cache = jax.tree.map(
            lambda c: jnp.broadcast_to(c[None], (cfg.num_layers,) + c.shape),
            cache)
        return {"cache": cache}
    if cfg.family == "ssm":
        rspec = blocks.rwkv_spec(cfg)
        L, D = cfg.num_layers, cfg.d_model
        H, hd = rspec.num_heads, rspec.head_dim
        return {"states": (
            jnp.zeros((L, batch, D), cfg.dtype),                # prev_tm
            jnp.zeros((L, batch, H, hd, hd), jnp.float32),      # wkv
            jnp.zeros((L, batch, D), cfg.dtype),                # prev_cm
        )}
    if cfg.family == "hybrid":
        groups, per = _hybrid_shape(cfg)
        mspec = blocks.mamba_spec(cfg)
        conv0, ssm0 = mamba.init_states(mspec, batch, dtype=cfg.dtype)
        conv = jax.tree.map(
            lambda s: jnp.broadcast_to(
                s[None, None], (groups, per) + s.shape).copy(), conv0)
        ssm = jnp.broadcast_to(
            ssm0[None, None], (groups, per) + ssm0.shape).copy()
        attn_cache = attention.init_cache(spec, batch, max_len,
                                          dtype=cfg.dtype)
        attn_cache = jax.tree.map(
            lambda c: jnp.broadcast_to(c[None], (groups,) + c.shape),
            attn_cache)
        return {"conv": conv, "ssm": ssm, "attn": attn_cache}
    if cfg.family == "audio":
        cache = {"self": attention.init_cache(spec, batch, max_len,
                                              dtype=cfg.dtype)}
        cache = jax.tree.map(
            lambda c: jnp.broadcast_to(c[None], (cfg.num_layers,) + c.shape),
            cache)
        enc_len = max(max_len // cfg.encoder_seq_divisor, 1)
        enc_len = min(enc_len, 8192)   # encoder memory is bounded (DESIGN §5)
        return {"cache": cache,
                "memory": jnp.zeros((batch, enc_len, cfg.d_model),
                                    cfg.dtype)}
    raise ValueError(cfg.family)


def decode_step(params, cfg: ModelConfig, state, tokens, positions):
    """One decode step.  tokens (B,1) i32, positions (B,) i32.
    Returns (logits (B,1,V), new_state)."""
    x = _embed(params, cfg, tokens)

    if cfg.family in ("dense", "vlm", "moe", "audio"):
        memory = state.get("memory")

        def body(h, ps):
            p, cache = ps
            h, new_cache = blocks.decoder_block_decode(
                p, cfg, h, cache, positions, memory=memory)
            return h, new_cache

        h, new_cache = jax.lax.scan(body, x,
                                    (params["layers"], state["cache"]))
        new_state = dict(state, cache=new_cache)

    elif cfg.family == "ssm":
        def body(h, ps):
            p, s = ps
            h, new_s = blocks.rwkv_block(p, cfg, h, state=s)
            return h, new_s
        h, new_states = jax.lax.scan(body, x,
                                     (params["layers"], state["states"]))
        new_state = {"states": new_states}

    elif cfg.family == "hybrid":
        def group(h, ps):
            p_group, conv_g, ssm_g, cache_g = ps
            h, new_cache = blocks.decoder_block_decode(
                params["shared"], cfg, h, {"self": cache_g}, positions)

            def inner(hh, qs):
                p, conv, ssm = qs
                hh, (new_conv, new_ssm) = blocks.mamba_block_decode(
                    p, cfg, hh, (conv, ssm))
                return hh, (new_conv, new_ssm)

            h, (new_conv_g, new_ssm_g) = jax.lax.scan(
                inner, h, (p_group, conv_g, ssm_g))
            return h, (new_conv_g, new_ssm_g, new_cache["self"])

        h, (new_conv, new_ssm, new_attn) = jax.lax.scan(
            group, x, (params["mamba"], state["conv"], state["ssm"],
                       state["attn"]))
        new_state = {"conv": new_conv, "ssm": new_ssm, "attn": new_attn}
    else:
        raise ValueError(cfg.family)

    h = layers.rmsnorm(params["ln_f"], h, eps=cfg.norm_eps)
    return _unembed_fn(params, cfg)(h), new_state


def prefill(params, cfg: ModelConfig, batch):
    """Score a full prompt and return the hidden states — the prefill_32k
    shape lowers this (labels-free forward)."""
    h, _ = forward(params, cfg, batch)
    return h
