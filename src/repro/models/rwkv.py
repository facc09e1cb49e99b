"""RWKV6 "Finch" blocks — attention-free linear recurrence with
data-dependent decay (arXiv:2404.05892).

Faithful to the defining Finch mechanics:

* token-shift mixing of the current and previous token,
* **data-dependent per-channel decay** ``w_t = exp(-exp(w0 + LoRA(x_t)))``
  (the paper's headline change over RWKV5's static decay),
* the ``u`` "bonus" for the current token,
* per-head WKV state ``S ∈ R^{head_dim × head_dim}``:
      y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T),
      S_t = diag(w_t) S_{t-1} + k_t v_t^T
* squared-ReLU channel mix.

Deliberate simplification (noted per DESIGN.md §10): the official Finch uses
a 5-way LoRA tower to make *all* the token-shift mixes data-dependent; we use
static learned mixes for r/k/v/g and reserve the LoRA for the decay ``w`` —
the component the paper's name refers to.  The recurrence itself is exact.

The time scan is ``jax.lax.scan`` over T (compact HLO for the 512-device
dry-run; a chunked-parallel form is a §Perf candidate).  Decode carries
(S, prev_token) per layer — O(1) in context length, which is why rwkv6 runs
the long_500k shape natively.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers


@dataclasses.dataclass(frozen=True)
class RWKVSpec:
    d_model: int
    d_ff: int
    head_dim: int = 64
    decay_lora_rank: int = 64

    @property
    def num_heads(self) -> int:
        return self.d_model // self.head_dim


def init(key, spec: RWKVSpec, *, dtype):
    D, F, H, hd = spec.d_model, spec.d_ff, spec.num_heads, spec.head_dim
    ks = jax.random.split(key, 12)
    return {
        "time_mix": {
            # token-shift mix coefficients (static; see docstring)
            "mix_r": jnp.full((D,), 0.5, dtype),
            "mix_k": jnp.full((D,), 0.5, dtype),
            "mix_v": jnp.full((D,), 0.5, dtype),
            "mix_g": jnp.full((D,), 0.5, dtype),
            "mix_w": jnp.full((D,), 0.5, dtype),
            "wr": layers.dense_init(ks[0], D, (H, hd), dtype=dtype),
            "wk": layers.dense_init(ks[1], D, (H, hd), dtype=dtype),
            "wv": layers.dense_init(ks[2], D, (H, hd), dtype=dtype),
            "wg": layers.dense_init(ks[3], D, (H, hd), dtype=dtype),
            "wo": layers.dense_init(ks[4], H * hd, D, dtype=dtype),
            # data-dependent decay: w_t = exp(-exp(w0 + tanh(x A) B))
            "w0": jnp.full((H, hd), -0.6, dtype),     # ~ decay 0.58
            "w_lora_a": layers.dense_init(ks[5], D, spec.decay_lora_rank,
                                          dtype=dtype),
            "w_lora_b": layers.truncated_normal_init(
                ks[6], (spec.decay_lora_rank, H, hd), 0.01, dtype),
            "u": layers.truncated_normal_init(ks[7], (H, hd), 0.1, dtype),
            "ln_x": layers.layernorm_init(H * hd, dtype=dtype),  # group norm
        },
        "channel_mix": {
            "mix_k": jnp.full((D,), 0.5, dtype),
            "mix_r": jnp.full((D,), 0.5, dtype),
            "wk": layers.dense_init(ks[8], D, F, dtype=dtype),
            "wv": layers.dense_init(ks[9], F, D, dtype=dtype),
            "wr": layers.dense_init(ks[10], D, D, dtype=dtype),
        },
    }


def _token_shift(x, prev):
    """shift right by one: position t sees token t-1; position 0 sees
    ``prev`` (zeros for training start, carried state for decode)."""
    return jnp.concatenate([prev[:, None, :], x[:, :-1, :]], axis=1)


def _mix(x, shifted, coeff):
    return x + (shifted - x) * coeff


def wkv_scan(r, k, v, w, u, state):
    """The WKV linear recurrence.

    r,k,v,w: (B, T, H, hd);  u: (H, hd);  state: (B, H, hd, hd).
    Returns (y (B,T,H,hd), final state).  f32 state for stability.
    """
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(S, inputs):
        r_t, k_t, v_t, w_t = inputs                     # (B, H, hd)
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)      # (B, H, hd, hd)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, S + uf[None, :, :, None] * kv)
        S_new = w_t[..., None] * S + kv
        return S_new, y

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, wf))
    final, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), final


def wkv_chunked(r, k, v, w, u, state, *, chunk: int = 64):
    """Chunked WKV — hillclimb iteration for the T-step scan (EXPERIMENTS
    §Perf/rwkv6): the per-token ``lax.scan`` costs 4096 sequential iterations
    at train_4k whose loop-carried copies dominated the memory roofline
    (measured 1.06e5 s).  This form processes ``chunk`` tokens per step with
    dense intra-chunk einsums (T/chunk steps).

    Numerics: all decay exponents appear as differences A_i - A_j with
    i >= j, so every exp() argument is <= 0 — no overflow for arbitrarily
    strong data-dependent decay (the factored r~ = r*exp(A) / k~ = k*exp(-A)
    matmul trick overflows for exactly that reason and is NOT used).

    Shapes as wkv_scan.  Exact (tests assert allclose vs wkv_scan).
    """
    B, T, H, hd = r.shape
    L = min(chunk, T)
    if T % L != 0:
        return wkv_scan(r, k, v, w, u, state)
    nC = T // L
    rf, kf, vf, wf = (t.astype(jnp.float32).reshape(B, nC, L, H, hd)
                      for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    strict = jnp.tril(jnp.ones((L, L), bool), k=-1)

    def chunk_step(S, inputs):
        r_c, k_c, v_c, w_c = inputs                  # (B, L, H, hd)
        log_w = jnp.log(jnp.maximum(w_c, 1e-30))
        A = jnp.cumsum(log_w, axis=1)                # inclusive
        A_prev = A - log_w                           # exclusive
        # intra-chunk pair decays D[i,j] = exp(A_{i-1} - A_j), j < i
        D = jnp.exp(A_prev[:, :, None] - A[:, None, :, :])  # (B,L,L,H,hd)
        D = jnp.where(strict[None, :, :, None, None], D, 0.0)
        scores = jnp.einsum("blhd,bmhd,blmhd->blmh", r_c, k_c, D)
        diag = jnp.einsum("blhd,hd,blhd->blh", r_c, uf, k_c)
        y_c = jnp.einsum("blmh,bmhd->blhd", scores, v_c) \
            + diag[..., None] * v_c
        # entering-state contribution + state update
        y_c = y_c + jnp.einsum("blhd,bhdv->blhv",
                               r_c * jnp.exp(A_prev), S)
        decay_end = jnp.exp(A[:, -1:, :] - A)
        kv_inj = jnp.einsum("blhd,blhv->bhdv", k_c * decay_end, v_c)
        S_new = jnp.exp(A[:, -1, :, :])[..., None] * S + kv_inj
        return S_new, y_c

    final, ys = jax.lax.scan(
        chunk_step, state.astype(jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, wf)))
    y = jnp.moveaxis(ys, 0, 1)                       # (B, nC, L, H, hd)
    return y.reshape(B, T, H, hd), final


def _wkv_dispatch(r, k, v, w, u, state):
    """Route the WKV chunked compute through shard_map when a mesh is
    ambient: batch -> data, heads -> model, zero internal collectives.

    Measured motivation (EXPERIMENTS §Perf/rwkv6 iteration 2): under plain
    GSPMD the (B, L, L, H, hd) intra-chunk decay tensor came out fully
    replicated (17.2 GB × 8192 scan iterations of phantom traffic) — the
    partitioner cannot infer sharding through the three-operand decay einsum.
    Inside shard_map every operand is already local, so the tensor is
    (B/16, L, L, H/16, hd) per device by construction."""
    from repro.models import meshctx
    from jax.sharding import PartitionSpec as P
    mesh = meshctx.current_mesh()
    B, T, H, hd = r.shape
    if mesh is not None and "model" in mesh.axis_names:
        dd = meshctx.dspec(mesh)
        dn = meshctx.data_size(mesh)
        mp = meshctx.model_size(mesh)
        if B % dn == 0 and H % mp == 0 and dd is not None:
            spec4 = P(dd, None, "model", None)
            # check_vma=False: the varying-axes checker mis-infers the
            # carry types when this region sits inside an outer lax.scan
            # (the layer stack / microbatch loops).
            return jax.shard_map(
                lambda *a: wkv_chunked(*a),
                mesh=mesh,
                in_specs=(spec4, spec4, spec4, spec4, P("model", None),
                          P(dd, "model", None, None)),
                out_specs=(spec4, P(dd, "model", None, None)),
                check_vma=False,
            )(r, k, v, w, u, state)
    return wkv_chunked(r, k, v, w, u, state)


def time_mix(params, spec: RWKVSpec, x, *, prev_token=None, wkv_state=None):
    """RWKV6 attention replacement.  x: (B,T,D).
    Returns (out, (new_prev_token, new_wkv_state))."""
    p = params
    B, T, D = x.shape
    H, hd = spec.num_heads, spec.head_dim
    if prev_token is None:
        prev_token = jnp.zeros((B, D), x.dtype)
    if wkv_state is None:
        wkv_state = jnp.zeros((B, H, hd, hd), jnp.float32)

    shifted = _token_shift(x, prev_token)
    xr = _mix(x, shifted, p["mix_r"])
    xk = _mix(x, shifted, p["mix_k"])
    xv = _mix(x, shifted, p["mix_v"])
    xg = _mix(x, shifted, p["mix_g"])
    xw = _mix(x, shifted, p["mix_w"])

    r = jnp.einsum("btd,dhk->bthk", xr, p["wr"])
    k = jnp.einsum("btd,dhk->bthk", xk, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", xv, p["wv"])
    g = jax.nn.silu(jnp.einsum("btd,dhk->bthk", xg, p["wg"])
                    .astype(jnp.float32)).astype(x.dtype)

    # data-dependent decay (the Finch contribution)
    lora = jnp.einsum("btr,rhk->bthk",
                      jnp.tanh(jnp.einsum("btd,dr->btr", xw, p["w_lora_a"])
                               .astype(jnp.float32)).astype(x.dtype),
                      p["w_lora_b"])
    w = jnp.exp(-jnp.exp((p["w0"][None, None] + lora).astype(jnp.float32)))

    if T > 1:
        y, new_state = _wkv_dispatch(r, k, v, w, p["u"], wkv_state)
    else:
        y, new_state = wkv_scan(r, k, v, w, p["u"], wkv_state)
    y = y.reshape(B, T, H * hd).astype(x.dtype)
    y = layers.layernorm(p["ln_x"], y)       # Finch's per-head group norm
    y = y * g.reshape(B, T, H * hd)
    out = jnp.einsum("btf,fd->btd", y, p["wo"])
    return out, (x[:, -1, :], new_state)


def channel_mix(params, spec: RWKVSpec, x, *, prev_token=None):
    """Squared-ReLU channel mixing.  Returns (out, new_prev_token)."""
    p = params
    B, T, D = x.shape
    if prev_token is None:
        prev_token = jnp.zeros((B, D), x.dtype)
    shifted = _token_shift(x, prev_token)
    xk = _mix(x, shifted, p["mix_k"])
    xr = _mix(x, shifted, p["mix_r"])
    k = jnp.einsum("btd,df->btf", xk, p["wk"])
    k = jnp.square(jax.nn.relu(k.astype(jnp.float32))).astype(x.dtype)
    r = jax.nn.sigmoid(jnp.einsum("btd,dd->btd", xr, p["wr"])
                       .astype(jnp.float32)).astype(x.dtype)
    return r * jnp.einsum("btf,fd->btd", k, p["wv"]), x[:, -1, :]
