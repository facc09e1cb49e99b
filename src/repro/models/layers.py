"""Shared neural-net layers (pure functional, params = nested dicts).

Conventions:
* ``init_*`` returns a params pytree; ``apply`` style functions are pure.
* Params are stored in ``param_dtype`` (default f32 at small scale, bf16 at
  production scale via configs); matmuls run in the activation dtype.
* Layer stacks are *scanned*: per-layer params carry a leading L axis
  (initialized with vmap) and the block is applied under ``jax.lax.scan`` —
  this keeps the HLO size O(1) in depth, which the 512-device dry-run
  compiles depend on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def truncated_normal_init(key, shape, scale: float, dtype):
    """He/LeCun-style scaled truncated normal."""
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (x * scale).astype(dtype)


def dense_init(key, d_in: int, d_out, *, dtype, scale: float | None = None):
    """Weight matrix (d_in, *d_out) with fan-in scaling."""
    if isinstance(d_out, int):
        d_out = (d_out,)
    scale = scale if scale is not None else d_in ** -0.5
    return truncated_normal_init(key, (d_in, *d_out), scale, dtype)


def embed_init(key, vocab: int, d_model: int, *, dtype):
    return truncated_normal_init(key, (vocab, d_model), 1.0, dtype)


# ---------------------------------------------------------------------------
# norms

def rmsnorm_init(dim: int, *, dtype):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params, x, *, eps: float = 1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * params["scale"].astype(jnp.float32)).astype(dtype)


def layernorm_init(dim: int, *, dtype):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params, x, *, eps: float = 1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    normed = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = normed * params["scale"].astype(jnp.float32) \
        + params["bias"].astype(jnp.float32)
    return out.astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings

def rope_frequencies(head_dim: int, *, theta: float = 1e4):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, *, theta: float = 1e4):
    """x: (..., T, H, head_dim); positions: broadcastable to (..., T)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta=theta)         # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., T, hd/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., T, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs

def swiglu_init(key, d_model: int, d_ff: int, *, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype=dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype=dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype=dtype),
    }


def _swiglu_local(w_gate, w_up, w_down, x):
    gate = jnp.einsum("...d,df->...f", x, w_gate)
    up = jnp.einsum("...d,df->...f", x, w_up)
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return jnp.einsum("...f,fd->...d", hidden, w_down)


import functools


@functools.lru_cache(maxsize=None)
def _make_swiglu_sp_region(data_axes: tuple):
    """Megatron SP+TP SwiGLU per-device body (runs inside shard_map), with a
    hand-written VJP (EXPERIMENTS §Perf/qwen2 iteration 3): the autodiff'd
    version moved f32 tangents through the gathers and lowered the
    all-gather transpose as a full-size ``psum_invariant`` all-reduce
    (604 MB × 320 occurrences on qwen2-72b).  Here every collective carries
    the residual dtype (bf16), the gather transpose is an explicit
    reduce-scatter, the gathered activations are re-gathered in the backward
    instead of saved, and the weight-grad data reduction is an explicit psum
    over ``data_axes``."""

    @jax.custom_vjp
    def region(w_gate, w_up, w_down, x_blk):
        g = jax.lax.all_gather(x_blk, "model", axis=1, tiled=True)
        out = _swiglu_local(w_gate, w_up, w_down, g)
        return jax.lax.psum_scatter(out.astype(x_blk.dtype), "model",
                                    scatter_dimension=1, tiled=True)

    def fwd(w_gate, w_up, w_down, x_blk):
        return region(w_gate, w_up, w_down, x_blk), \
            (w_gate, w_up, w_down, x_blk)

    def bwd(res, grad_out):
        w_gate, w_up, w_down, x_blk = res
        g = jax.lax.all_gather(x_blk, "model", axis=1, tiled=True)
        go = jax.lax.all_gather(grad_out, "model", axis=1, tiled=True)
        gate = jnp.einsum("...d,df->...f", g, w_gate)
        up = jnp.einsum("...d,df->...f", g, w_up)
        gate32 = gate.astype(jnp.float32)
        sg = jax.nn.silu(gate32)
        h = sg.astype(g.dtype) * up

        grad_h = jnp.einsum("...d,fd->...f", go, w_down)
        grad_wd = jnp.einsum("...f,...d->fd", h, go)
        grad_up = grad_h * sg.astype(grad_h.dtype)
        sig = jax.nn.sigmoid(gate32)
        dsilu = sig * (1 + gate32 * (1 - sig))
        grad_gate = (grad_h.astype(jnp.float32) * up.astype(jnp.float32)
                     * dsilu).astype(g.dtype)
        grad_g = jnp.einsum("...f,df->...d", grad_gate, w_gate) \
            + jnp.einsum("...f,df->...d", grad_up, w_up)
        grad_x = jax.lax.psum_scatter(grad_g.astype(x_blk.dtype), "model",
                                      scatter_dimension=1, tiled=True)
        grad_wg = jnp.einsum("...d,...f->df", g, grad_gate)
        grad_wu = jnp.einsum("...d,...f->df", g, grad_up)
        # explicit data-parallel weight-grad reduction (vma correctness)
        grad_wg, grad_wu, grad_wd = jax.lax.psum(
            (grad_wg, grad_wu, grad_wd), axis_name=data_axes)
        return grad_wg, grad_wu, grad_wd, grad_x

    region.defvjp(fwd, bwd)
    return region


def swiglu(params, x):
    """SwiGLU MLP.  Under an ambient mesh with sequence-parallel activations
    this runs the Megatron SP+TP schedule in shard_map: all-gather the
    T-sharded residual over ``model``, compute against the F-sharded expert
    of d_ff, reduce-scatter the partial output back to T-sharded — activation
    traffic 2·B·T·D per layer instead of gathering the (much larger) 3·D·F
    weights per use (measured 2.3 TB/device/step of ZeRO-3 weight gathers on
    qwen2-72b; see EXPERIMENTS.md §Perf iteration 2)."""
    from repro.models import meshctx
    mesh = meshctx.current_mesh()
    if x.ndim == 3 and mesh is not None:
        B, T, D = x.shape
        F = params["w_gate"].shape[-1]
        mp = meshctx.model_size(mesh)
        if (meshctx.sp_applicable(mesh, B, T) and F % mp == 0):
            from jax.sharding import PartitionSpec as P
            dd = meshctx.dspec(mesh)
            region = _make_swiglu_sp_region(meshctx.data_axes(mesh))
            return jax.shard_map(
                region, mesh=mesh,
                in_specs=(P(None, "model"), P(None, "model"),
                          P("model", None), P(dd, "model", None)),
                out_specs=P(dd, "model", None),
            )(params["w_gate"], params["w_up"], params["w_down"], x)
    return _swiglu_local(params["w_gate"], params["w_up"],
                         params["w_down"], x)


def gelu_mlp_init(key, d_model: int, d_ff: int, *, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w_in": dense_init(k1, d_model, d_ff, dtype=dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(k2, d_ff, d_model, dtype=dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }


def gelu_mlp(params, x):
    h = jnp.einsum("...d,df->...f", x, params["w_in"]) + params["b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, params["w_out"]) + params["b_out"]


# ---------------------------------------------------------------------------
# losses

def cross_entropy_loss(logits_fn, hidden, labels, *, vocab_chunk: int = 0,
                       ignore_index: int = -1):
    """Memory-frugal LM cross entropy.

    ``logits_fn(h_chunk) -> (..., V)`` is applied to sequence chunks under a
    scan so the full (B, T, V) logits tensor never materializes (critical for
    the 150k-vocab configs at 32k context).

    hidden: (B, T, D); labels: (B, T) int32 with ``ignore_index`` masked out.
    Returns mean loss over unmasked positions.
    """
    B, T = labels.shape
    chunk = vocab_chunk if vocab_chunk > 0 else min(T, 512)
    n_chunks = T // chunk if T % chunk == 0 else 1
    if T % chunk != 0:
        chunk = T

    h = hidden.reshape(B, n_chunks, chunk, hidden.shape[-1]) \
        .transpose(1, 0, 2, 3)
    y = labels.reshape(B, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        total, count = carry
        hc, yc = xs
        logits = logits_fn(hc).astype(jnp.float32)          # (B, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        mask = (yc != ignore_index)
        safe_y = jnp.where(mask, yc, 0)
        picked = jnp.take_along_axis(
            logits, safe_y[..., None], axis=-1)[..., 0]
        nll = jnp.where(mask, lse - picked, 0.0)
        return (total + jnp.sum(nll),
                count + jnp.sum(mask.astype(jnp.float32))), None

    (total, count), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                            jnp.zeros((), jnp.float32)),
                                     (h, y))
    return total / jnp.maximum(count, 1.0)
