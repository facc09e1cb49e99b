"""Grouped-query attention with the knobs the assigned archs need.

Features: GQA (num_kv_heads <= num_heads), optional QKV bias (Qwen2), optional
q/k RMSNorm (Qwen3), RoPE, causal masking, sliding-window attention (H2O
Danube3; and the long_500k variant for the other dense archs), bidirectional
mode (encoders), cross-attention (Seamless enc-dec), and a single-token decode
path against a KV cache.

The core score/softmax/value computation is factored into ``attention_core``
(and its memory-bounded twin ``attention_core_blocked``), so a fused kernel
can replace it 1:1; tests/test_attention_core.py checks both against an
independent float64 masked softmax.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    causal: bool = True
    sliding_window: int | None = None
    rope_theta: float = 1e4
    cross: bool = False        # cross-attention: kv from encoder memory

    @property
    def group_size(self) -> int:
        return self.num_heads // self.num_kv_heads


def init(key, spec: AttentionSpec, *, dtype):
    ks = jax.random.split(key, 5)
    H, KV, hd, D = (spec.num_heads, spec.num_kv_heads, spec.head_dim,
                    spec.d_model)
    p = {
        "wq": layers.dense_init(ks[0], D, (H, hd), dtype=dtype),
        "wk": layers.dense_init(ks[1], D, (KV, hd), dtype=dtype),
        "wv": layers.dense_init(ks[2], D, (KV, hd), dtype=dtype),
        "wo": layers.dense_init(ks[3], H * hd, D, dtype=dtype,
                                scale=(H * hd) ** -0.5),
    }
    if spec.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype)
        p["bk"] = jnp.zeros((KV, hd), dtype)
        p["bv"] = jnp.zeros((KV, hd), dtype)
    if spec.qk_norm:
        p["q_norm"] = layers.rmsnorm_init(hd, dtype=dtype)
        p["k_norm"] = layers.rmsnorm_init(hd, dtype=dtype)
    return p


def _project_q(params, spec: AttentionSpec, x, positions):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    if spec.qkv_bias:
        q = q + params["bq"]
    if spec.qk_norm:
        q = layers.rmsnorm(params["q_norm"], q)
    if not spec.cross:
        q = layers.apply_rope(q, positions, theta=spec.rope_theta)
    return q


def _project_kv(params, spec: AttentionSpec, x, positions):
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if spec.qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    if spec.qk_norm:
        k = layers.rmsnorm(params["k_norm"], k)
    if not spec.cross:
        k = layers.apply_rope(k, positions, theta=spec.rope_theta)
    return k, v


def attention_core(q, k, v, *, causal: bool, sliding_window: int | None,
                   q_positions=None, kv_positions=None,
                   kv_valid_len=None):
    """Scores/softmax/values for GQA.

    q: (B, Tq, H, hd);  k: (B, Tk, KV, hd);  v: (B, Tk, KV, hv), whose head
    dim may differ from q/k's (latent attention: 192 against 128).  Head
    grouping is done by reshaping q to (B, Tq, KV, G, hd) — no
    repeat/materialization of kv.  Returns (B, Tq, H, hv).

    ``q_positions``/``kv_positions`` (B, T) default to arange (prefill);
    decode passes explicit positions.  ``kv_valid_len`` (B,) masks cache tail.
    """
    B, Tq, H, hd = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Tq, KV, G, hd)
    scale = hd ** -0.5

    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    scores = scores * scale                                  # (B,KV,G,Tq,Tk)

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Tq)[None], (B, Tq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(Tk)[None], (B, Tk))
    qp = q_positions[:, None, None, :, None]                 # (B,1,1,Tq,1)
    kp = kv_positions[:, None, None, None, :]                # (B,1,1,1,Tk)

    mask = jnp.ones((B, 1, 1, Tq, Tk), bool)
    if causal:
        mask = mask & (kp <= qp)
    if sliding_window is not None:
        mask = mask & (kp > qp - sliding_window)
    if kv_valid_len is not None:
        valid = kv_positions < kv_valid_len[:, None]
        mask = mask & valid[:, None, None, None, :]

    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Tq, H, v.shape[-1])


def attention_core_blocked(q, k, v, *, causal: bool,
                           sliding_window: int | None,
                           q_block: int = 512):
    """Memory-bounded attention: Python-unrolled loop over q blocks, each
    attending only to its *statically sliced* causal/window kv prefix.

    This is the XLA-side realization of flash attention's blocking: the
    (Tq, Tk) score matrix never materializes — peak intermediate is
    (q_block, kv_slice) per head — and, because the loop is unrolled with
    static slices, the lowered HLO contains exactly the useful dot ops (no
    masked-out wasted compute beyond block granularity), which keeps the
    dry-run roofline honest.  Gradients flow through normally.

    Requires default positions (prefill layout, q_pos == kv_pos == arange).
    v's head dim may differ from q/k's, as in ``attention_core``.
    """
    B, Tq, H, hd = q.shape
    Tk = k.shape[1]
    assert Tq == Tk, "blocked path assumes self-attention prefill layout"
    q_block = min(q_block, Tq)
    n_blocks = (Tq + q_block - 1) // q_block
    outs = []
    for i in range(n_blocks):
        qs, qe = i * q_block, min((i + 1) * q_block, Tq)
        ks = 0
        ke = qe if causal else Tk
        if sliding_window is not None:
            ks = max(0, qs - sliding_window + 1)
        q_blk = q[:, qs:qe]
        k_blk = k[:, ks:ke]
        v_blk = v[:, ks:ke]
        qpos = jnp.broadcast_to(jnp.arange(qs, qe)[None], (B, qe - qs))
        kpos = jnp.broadcast_to(jnp.arange(ks, ke)[None], (B, ke - ks))
        outs.append(attention_core(
            q_blk, k_blk, v_blk, causal=causal,
            sliding_window=sliding_window,
            q_positions=qpos, kv_positions=kpos))
    return jnp.concatenate(outs, axis=1)


# blocked path kicks in above this many query positions (train/prefill)
BLOCKED_ATTENTION_THRESHOLD = 2048


def _online_softmax_attention(q, k, v, *, causal, window, q_pos, kv_block,
                              kv_len):
    """Flash-style online softmax over kv blocks (pure jnp, static loop).

    q: (B, Tq, H, hd) — a query block; k/v: (B, Tk, KV, hd) full;
    q_pos: (B, Tq) absolute positions (traced OK).  Returns (B, Tq, H, hd).

    The static python loop over kv blocks keeps the peak intermediate at
    (Tq, kv_block) scores per head — the XLA analogue of a flash kernel's
    VMEM tiling, and exact-FLOP-visible to the dry-run roofline.
    """
    B, Tq, H, hd = q.shape
    Tk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, Tq, KV, G, hd)
    scale = hd ** -0.5
    m = jnp.full((B, KV, G, Tq), -1e30, jnp.float32)
    l = jnp.zeros((B, KV, G, Tq), jnp.float32)
    acc = jnp.zeros((B, Tq, KV, G, hd), jnp.float32)
    n_blocks = (Tk + kv_block - 1) // kv_block
    for i in range(n_blocks):
        ks_, ke_ = i * kv_block, min((i + 1) * kv_block, Tk)
        kb = k[:, ks_:ke_]
        vb = v[:, ks_:ke_]
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, kb).astype(jnp.float32)
        s = s * scale
        kv_pos = jnp.arange(ks_, ke_)
        mask = jnp.ones((B, 1, 1, Tq, ke_ - ks_), bool)
        qp = q_pos[:, None, None, :, None]
        kp = kv_pos[None, None, None, None, :]
        if causal:
            mask = mask & (kp <= qp)
        if window is not None:
            mask = mask & (kp > qp - window)
        if kv_len is not None:
            mask = mask & (kp < kv_len[:, None, None, None, None])
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), vb)
        acc = acc * jnp.moveaxis(alpha, 3, 1)[..., None] \
            + pv.astype(jnp.float32)
        m = m_new
    denom = jnp.maximum(jnp.moveaxis(l, 3, 1), 1e-30)[..., None]
    return (acc / denom).reshape(B, Tq, H, hd)


def apply_sequence_parallel(params, spec: AttentionSpec, x, *, memory=None,
                            q_block: int = 256, kv_block: int = 1024):
    """Sequence-parallel attention under shard_map (the production path).

    Motivation: naive GSPMD head
    sharding collapses for GQA (num_kv_heads < |model|) and non-divisible
    head counts (minitron 24H, qwen3 40H): the partitioner reshards the
    (B, KV, G, Tq, Tk) score tensors across the contracting dims, emitting
    ~7 GB all-reduces per layer (~14 TB/device/step on qwen2-72b).

    Design: the query positions are sharded over ``model`` (T/|model| per
    rank); k/v are projected locally from each rank's chunk and all-gathered
    over ``model`` (GQA makes kv 2·KV·hd/D ≈ 4-8× smaller than gathering x).
    All score/softmax/value compute is then rank-local with zero further
    collectives, for ANY head count.  Known baseline cost: causal masking is
    applied, not exploited — every rank scans the full kv (≈2× score FLOPs
    waste); recorded as a §Perf candidate (ragged kv bounds).
    """
    from repro.models import meshctx
    from jax.sharding import PartitionSpec as P
    mesh = meshctx.current_mesh()
    B, T, D = x.shape
    dd = meshctx.dspec(mesh)
    mp = meshctx.model_size(mesh)
    t_loc = T // mp
    causal = spec.causal and not spec.cross
    window = spec.sliding_window if not spec.cross else None

    def body(p, x_blk, mem_blk):
        b_loc = x_blk.shape[0]
        offset = jax.lax.axis_index("model") * t_loc
        q_pos_full = offset + jnp.arange(t_loc)
        q = _project_q(p, spec, x_blk,
                       jnp.broadcast_to(q_pos_full[None], (b_loc, t_loc)))
        if spec.cross:
            s_len = mem_blk.shape[1]
            k, v = _project_kv(p, spec, mem_blk, None)
        else:
            kv_pos = jnp.broadcast_to(q_pos_full[None], (b_loc, t_loc))
            k_loc, v_loc = _project_kv(p, spec, x_blk, kv_pos)
            k = jax.lax.all_gather(k_loc, "model", axis=1, tiled=True)
            v = jax.lax.all_gather(v_loc, "model", axis=1, tiled=True)
        outs = []
        n_q = (t_loc + q_block - 1) // q_block
        for i in range(n_q):
            qs_, qe_ = i * q_block, min((i + 1) * q_block, t_loc)
            outs.append(_online_softmax_attention(
                q[:, qs_:qe_], k, v, causal=causal, window=window,
                q_pos=jnp.broadcast_to(
                    (offset + jnp.arange(qs_, qe_))[None],
                    (b_loc, qe_ - qs_)),
                kv_block=kv_block, kv_len=None))
        out = jnp.concatenate(outs, axis=1).astype(x_blk.dtype)
        out = out.reshape(b_loc, t_loc, spec.num_heads * spec.head_dim)
        return jnp.einsum("btf,fd->btd", out, p["wo"])

    mem_spec = P(dd, None, None)
    if memory is None:
        memory = jnp.zeros((B, 1, 1), x.dtype)   # placeholder, unused
    shmap = jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), params),
                  P(dd, "model", None), mem_spec),
        out_specs=P(dd, "model", None))
    return shmap(params, x, memory)


def apply(params, spec: AttentionSpec, x, *, memory=None, positions=None,
          segment_mask=None):
    """Full-sequence attention (train / prefill).

    ``memory`` (B, S, D) supplies kv for cross-attention.  Returns (B, T, D).
    """
    B, T, _ = x.shape
    from repro.models import meshctx
    mesh = meshctx.current_mesh()
    if positions is None and meshctx.sp_applicable(mesh, B, T) \
            and (memory is None or
                 memory.shape[0] % meshctx.data_size(mesh) == 0):
        return apply_sequence_parallel(params, spec, x, memory=memory)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    kv_src = memory if spec.cross else x
    kv_pos = (jnp.broadcast_to(jnp.arange(kv_src.shape[1])[None],
                               (B, kv_src.shape[1]))
              if spec.cross else positions)
    q = _project_q(params, spec, x, positions)
    k, v = _project_kv(params, spec, kv_src, kv_pos)
    causal = spec.causal and not spec.cross
    window = spec.sliding_window if not spec.cross else None
    if (not spec.cross and T > BLOCKED_ATTENTION_THRESHOLD
            and k.shape[1] == T):
        out = attention_core_blocked(q, k, v, causal=causal,
                                     sliding_window=window)
    else:
        out = attention_core(
            q, k, v, causal=causal, sliding_window=window,
            q_positions=positions, kv_positions=kv_pos)
    out = out.reshape(B, T, spec.num_heads * spec.head_dim)
    return jnp.einsum("btf,fd->btd", out, params["wo"])


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2 §2.1, arXiv:2405.04434; as used
# by DeepSeek-V3), without query compression

@dataclasses.dataclass(frozen=True)
class MLASpec:
    d_model: int
    num_heads: int
    kv_lora_rank: int          # width of the compressed KV latent
    qk_nope_head_dim: int      # per-head query/key dims without rotary
    qk_rope_head_dim: int      # rotary dims; the key's are shared by heads
    v_head_dim: int
    rope_theta: float = 1e4
    latent_norm_eps: float = 1e-6

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def mla_init(key, spec: MLASpec, *, dtype):
    ks = jax.random.split(key, 4)
    D, H, R = spec.d_model, spec.num_heads, spec.kv_lora_rank
    hv = spec.v_head_dim
    return {
        "wq": layers.dense_init(ks[0], D, (H, spec.qk_head_dim), dtype=dtype),
        # the latent and the shared rotary key, in one projection
        "wkv_a": layers.dense_init(ks[1], D, R + spec.qk_rope_head_dim,
                                   dtype=dtype),
        "kv_norm": layers.rmsnorm_init(R, dtype=dtype),
        # per-head keys (without rotary) and values from the latent
        "wkv_b": layers.dense_init(ks[2], R, (H, spec.qk_nope_head_dim + hv),
                                   dtype=dtype),
        "wo": layers.dense_init(ks[3], H * hv, D, dtype=dtype,
                                scale=(H * hv) ** -0.5),
    }


def mla_apply(params, spec: MLASpec, x):
    """Causal latent self-attention over positions 0..T-1 (train /
    prefill).  x: (B, T, D) -> (B, T, D).  The keys are
    [k_nope_h, rope(k_rope)] with ``k_rope`` one (B, T, rope) projection
    shared by every head; the scores' scale is ``qk_head_dim ** -0.5``.
    The projections and the core run under the ``mla`` scope."""
    B, T, _ = x.shape
    H, nope, R = spec.num_heads, spec.qk_nope_head_dim, spec.kv_lora_rank
    with jax.named_scope("mla"):
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
        q_rope = layers.apply_rope(q[..., nope:], positions,
                                   theta=spec.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        kv_a = jnp.einsum("btd,dr->btr", x, params["wkv_a"])
        latent = layers.rmsnorm(params["kv_norm"], kv_a[..., :R],
                                eps=spec.latent_norm_eps)
        k_rope = layers.apply_rope(kv_a[:, :, None, R:], positions,
                                   theta=spec.rope_theta)     # (B, T, 1, r)
        kv = jnp.einsum("btr,rhk->bthk", latent, params["wkv_b"])
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, (B, T, H, spec.qk_rope_head_dim))],
            axis=-1)
        v = kv[..., nope:]
        if T > BLOCKED_ATTENTION_THRESHOLD:
            out = attention_core_blocked(q, k, v, causal=True,
                                         sliding_window=None)
        else:
            out = attention_core(q, k, v, causal=True, sliding_window=None)
        out = out.reshape(B, T, H * spec.v_head_dim)
        return jnp.einsum("btf,fd->btd", out, params["wo"])


# ---------------------------------------------------------------------------
# decode path

def cache_shape(spec: AttentionSpec, batch: int, max_len: int):
    """Physical cache length: a sliding window needs only ``window`` slots
    (ring buffer) — this is what makes long_500k decode sub-quadratic AND
    sub-linear in memory for SWA archs."""
    phys = max_len if spec.sliding_window is None \
        else min(max_len, spec.sliding_window)
    return (batch, phys, spec.num_kv_heads, spec.head_dim)


def init_cache(spec: AttentionSpec, batch: int, max_len: int, *, dtype):
    shape = cache_shape(spec, batch, max_len)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_step(params, spec: AttentionSpec, x, cache, position, *,
                memory=None):
    """One-token decode.  x: (B, 1, D); position: (B,) int32 — the absolute
    position of this token.  Returns (out (B,1,D), new_cache)."""
    B = x.shape[0]
    if spec.cross:
        # cross-attention: kv comes from fixed encoder memory; nothing cached
        # per-step (memory is precomputed outside).
        k, v = _project_kv(params, spec, memory, None)
        q = _project_q(params, spec, x, position[:, None])
        out = attention_core(q, k, v, causal=False, sliding_window=None,
                             q_positions=position[:, None])
        out = out.reshape(B, 1, spec.num_heads * spec.head_dim)
        return jnp.einsum("btf,fd->btd", out, params["wo"]), cache

    q = _project_q(params, spec, x, position[:, None])
    k_new, v_new = _project_kv(params, spec, x, position[:, None])

    phys = cache["k"].shape[1]
    slot = (position % phys)                                  # ring for SWA
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k_new[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[bidx, slot].set(v_new[:, 0].astype(cache["v"].dtype))

    # absolute positions of every physical slot (ring-aware): slot s holds
    # the most recent token congruent to s mod phys that is <= position.
    slots = jnp.arange(phys)[None, :]                         # (1, phys)
    pos_col = position[:, None]
    kv_positions = pos_col - ((pos_col - slots) % phys)       # (B, phys)
    valid = kv_positions >= 0
    if spec.sliding_window is not None:
        valid = valid & (kv_positions > pos_col - spec.sliding_window)

    out = attention_core(
        q, k_cache, v_cache, causal=True,
        sliding_window=spec.sliding_window,
        q_positions=position[:, None],
        kv_positions=jnp.where(valid, kv_positions, jnp.int32(1) << 30))
    out = out.reshape(B, 1, spec.num_heads * spec.head_dim)
    return (jnp.einsum("btf,fd->btd", out, params["wo"]),
            {"k": k_cache, "v": v_cache})
