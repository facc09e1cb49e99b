"""Mixture-of-Experts layer (top-k router, capacity-bounded dispatch).

TPU adaptation notes (DESIGN.md §3): expert dispatch uses sorted scatter into
per-expert capacity buffers rather than the (tokens × experts × capacity)
one-hot einsum of GShard — the one-hot dispatch tensor is infeasible at
kimi-k2 scale (1M tokens × 384 experts).  Scatter/gather lower to
all-to-all-style collectives when the expert axis is sharded over ``model``
(expert parallelism), which is exactly the collective the roofline tracks.

Tokens beyond an expert's capacity are dropped (standard; capacity_factor
controls the slack).  The router adds the usual load-balance auxiliary loss
(Switch/GShard form) and optional router z-loss.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers, meshctx


@dataclasses.dataclass(frozen=True)
class MoESpec:
    d_model: int
    d_ff: int                  # per-expert hidden size
    num_experts: int
    experts_per_token: int
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    router_z_weight: float = 1e-4


def init(key, spec: MoESpec, *, dtype):
    k_router, k_gate, k_up, k_down = jax.random.split(key, 4)
    E, D, F = spec.num_experts, spec.d_model, spec.d_ff

    def expert_init(k, d_in, d_out):
        return layers.truncated_normal_init(
            k, (E, d_in, d_out), d_in ** -0.5, dtype)

    return {
        "router": layers.dense_init(k_router, D, E, dtype=jnp.float32),
        "w_gate": expert_init(k_gate, D, F),
        "w_up": expert_init(k_up, D, F),
        "w_down": expert_init(k_down, F, D),
    }


def _capacity(spec: MoESpec, num_tokens: int) -> int:
    cap = int(spec.capacity_factor * num_tokens
              * spec.experts_per_token / spec.num_experts)
    return max(cap, spec.experts_per_token)


def route(params, spec: MoESpec, x_flat):
    """Router: logits, top-k ids/weights and aux losses.  x_flat: (N, D)."""
    logits = jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32),
                        params["router"])                      # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, spec.experts_per_token)
    top_w = top_w / jnp.maximum(
        jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)          # renormalize

    # Switch-style load-balance loss: E * sum_e f_e * p_e
    f = jnp.zeros((spec.num_experts,), jnp.float32).at[
        top_ids.reshape(-1)].add(1.0) / top_ids.size
    p = jnp.mean(probs, axis=0)
    aux = spec.num_experts * jnp.sum(f * p)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return top_ids, top_w, aux, z


def _ambient_mesh():
    return meshctx.current_mesh()


def _ep_applicable(spec: MoESpec, x, mesh) -> bool:
    if mesh is None or "model" not in mesh.axis_names:
        return False
    model_n = mesh.shape["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not data_axes:
        return False
    data_n = 1
    for a in data_axes:
        data_n *= mesh.shape[a]
    B = x.shape[0]
    return (spec.num_experts % model_n == 0 and B % data_n == 0
            and spec.num_experts >= model_n)


def apply(params, spec: MoESpec, x):
    """x: (B, T, D) -> (out (B, T, D), aux_loss scalar).

    Under an ambient mesh with a ``model`` axis (jax.set_mesh), dispatch runs
    **expert-parallel under shard_map**: each (data, model) device routes its
    local tokens to its local E/|model| experts in a per-device capacity
    buffer and the expert outputs are summed with one psum over ``model`` —
    the token→expert data movement is absorbed into the existing
    tensor-parallel all-reduce, and no global (E, C, D) buffer or
    GSPMD-replicated scatter ever exists (that naive lowering cost ~1 TB/chip
    of all-reduce on granite-moe; see EXPERIMENTS.md §Perf).

    Without a mesh (CPU tests / single device) the dense scatter path runs.
    """
    mesh = _ambient_mesh()
    if _ep_applicable(spec, x, mesh):
        return _apply_expert_parallel(params, spec, x, mesh)
    return _apply_dense(params, spec, x)


def _expert_ffn(w_gate, w_up, w_down, h):
    g = jnp.einsum("cd,df->cf", h, w_gate)
    u = jnp.einsum("cd,df->cf", h, w_up)
    act = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
    return jnp.einsum("cf,fd->cd", act, w_down)


def _dispatch_local(spec: MoESpec, x_flat, top_ids, top_w, *,
                    expert_lo: int, num_local: int, capacity: int):
    """Capacity-bounded dispatch of local tokens to local experts.
    Returns (expert_in (E_loc, C, D), combine info)."""
    N, D = x_flat.shape
    K = spec.experts_per_token
    flat_ids = top_ids.reshape(-1)
    local = (flat_ids >= expert_lo) & (flat_ids < expert_lo + num_local)
    le = jnp.where(local, flat_ids - expert_lo, num_local)  # sentinel bucket
    order = jnp.argsort(le, stable=True)
    sorted_le = le[order]
    first = jnp.searchsorted(sorted_le, sorted_le, side="left")
    rank_sorted = jnp.arange(N * K) - first
    slots = jnp.zeros((N * K,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = local & (slots < capacity)
    token_idx = jnp.repeat(jnp.arange(N), K)
    safe_e = jnp.where(keep, le, 0)
    safe_s = jnp.where(keep, slots, capacity - 1)
    contrib = jnp.where(keep[:, None], x_flat[token_idx], 0.0)
    expert_in = jnp.zeros((num_local, capacity, D), x_flat.dtype) \
        .at[safe_e, safe_s].add(contrib)
    w = jnp.where(keep, top_w.reshape(-1), 0.0)
    return expert_in, (token_idx, safe_e, safe_s, w)


def _apply_expert_parallel(params, spec: MoESpec, x, mesh):
    """Expert parallelism under shard_map.

    The residual stream arrives **T-sharded over model** (sequence
    parallelism); the body all-gathers x over ``model`` (bf16, B·T·D/|data|),
    routes its tokens to its E/|model| local experts, and returns the partial
    outputs with one ``psum_scatter`` back to T-sharded layout.  Explicitly
    managing the SP↔EP boundary this way replaced a GSPMD reshard that
    all-reduced the *unsharded* group activations per MoE layer (3.8 GB ×
    244 occurrences on kimi-k2 train_4k — EXPERIMENTS §Perf iteration 2)."""
    from jax.sharding import PartitionSpec as P
    B, T, D = x.shape
    E = spec.num_experts
    model_n = mesh.shape["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    d_ax = data_axes if len(data_axes) > 1 else data_axes[0]
    data_n = 1
    for a in data_axes:
        data_n *= mesh.shape[a]
    e_loc = E // model_n
    n_loc = (B // data_n) * T
    cap = max(int(spec.capacity_factor * n_loc
                  * spec.experts_per_token / E), spec.experts_per_token)
    t_sharded = (T % model_n == 0 and T >= model_n)

    # FSDP dim of the expert weights (mirrors launch/sharding.py's rule:
    # largest dim after E).  Gathering it EXPLICITLY inside the region makes
    # the gather's transpose a reduce-scatter into the optimizer layout —
    # the implicit jit-boundary reshard was hoisted out of the layer scan
    # (~129 GB resident weights) and its transpose lowered as a 4.2 GB × 244
    # in-loop all-reduce on kimi-k2 (EXPERIMENTS §Perf iteration 3).
    D_, F_ = params["w_gate"].shape[-2:]
    gate_fsdp_axis = 1 if D_ >= F_ else 2          # (E, D, F)
    down_fsdp_axis = 2 if D_ >= F_ else 1          # (E, F, D)
    fsdp_ok = (max(D_, F_) % data_n == 0 and max(D_, F_) >= data_n)

    def _wspec(ax):
        if not fsdp_ok:
            return P("model", None, None)
        spec_ = [None, None, None]
        spec_[0] = "model"
        spec_[ax] = d_ax
        return P(*spec_)

    def body(router_w, w_gate, w_up, w_down, x_blk):
        # x_blk: (B_loc, T/|model|, D) T-sharded (or (B_loc, T, D) if not)
        if fsdp_ok:
            w_gate = jax.lax.all_gather(w_gate, d_ax, axis=gate_fsdp_axis,
                                        tiled=True)
            w_up = jax.lax.all_gather(w_up, d_ax, axis=gate_fsdp_axis,
                                      tiled=True)
            w_down = jax.lax.all_gather(w_down, d_ax, axis=down_fsdp_axis,
                                        tiled=True)
        if t_sharded:
            x_blk = jax.lax.all_gather(x_blk, "model", axis=1, tiled=True)
        b_loc = x_blk.shape[0]
        x_flat = x_blk.reshape(b_loc * T, D)
        logits = jnp.einsum("nd,de->ne", x_flat.astype(jnp.float32),
                            router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_ids = jax.lax.top_k(probs, spec.experts_per_token)
        top_w = top_w / jnp.maximum(
            jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

        midx = jax.lax.axis_index("model")
        expert_lo = midx * e_loc
        expert_in, (token_idx, safe_e, safe_s, w) = _dispatch_local(
            spec, x_flat, top_ids, top_w,
            expert_lo=expert_lo, num_local=e_loc, capacity=cap)
        expert_out = jax.vmap(_expert_ffn)(w_gate, w_up, w_down, expert_in)
        gathered = expert_out[safe_e, safe_s]
        out_flat = jnp.zeros((b_loc * T, D), jnp.float32).at[token_idx].add(
            gathered.astype(jnp.float32) * w[:, None])
        # sum expert contributions across the model axis; scatter back to
        # the T-sharded layout when the stream is sequence-parallel
        if not t_sharded:
            out_flat = jax.lax.psum(out_flat, axis_name="model")
        if t_sharded:
            out_seq = out_flat.reshape(b_loc, T, D)
            out_seq = jax.lax.psum_scatter(out_seq, "model",
                                           scatter_dimension=1, tiled=True)
            out_flat = out_seq.reshape(b_loc * (T // model_n), D)

        # global router stats for the aux losses
        # stats are identical across model ranks only after the t_sharded
        # gather (then vma still marks them varying -> psum+divide); without
        # the gather they are invarying over model and must not be psum'd.
        stat_axes = data_axes + (("model",) if t_sharded else ())
        stat_norm = model_n if t_sharded else 1
        counts = jnp.zeros((E,), jnp.float32).at[top_ids.reshape(-1)].add(1.0)
        counts = jax.lax.psum(counts, axis_name=stat_axes) / stat_norm
        p_sum = jax.lax.psum(jnp.sum(probs, axis=0),
                             axis_name=stat_axes) / stat_norm
        n_tot = b_loc * T * data_n
        f = counts / (n_tot * spec.experts_per_token)
        p = p_sum / n_tot
        aux = E * jnp.sum(f * p)
        z = jax.lax.psum(
            jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
            axis_name=stat_axes) / stat_norm / n_tot
        t_out = T // model_n if t_sharded else T
        return (out_flat.astype(x_blk.dtype).reshape(b_loc, t_out, D),
                spec.router_aux_weight * aux + spec.router_z_weight * z)

    x_spec = P(d_ax, "model", None) if t_sharded else P(d_ax, None, None)
    shmap = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), _wspec(gate_fsdp_axis), _wspec(gate_fsdp_axis),
                  _wspec(down_fsdp_axis), x_spec),
        out_specs=(x_spec, P()),
    )
    return shmap(params["router"], params["w_gate"], params["w_up"],
                 params["w_down"], x)


def _apply_dense(params, spec: MoESpec, x):
    """x: (B, T, D) -> (out (B, T, D), aux_loss scalar)."""
    B, T, D = x.shape
    N = B * T
    K = spec.experts_per_token
    E = spec.num_experts
    C = _capacity(spec, N)
    x_flat = x.reshape(N, D)

    top_ids, top_w, aux, z = route(params, spec, x_flat)       # (N,K)

    # --- dispatch: rank each (token, k) assignment within its expert -------
    flat_ids = top_ids.reshape(-1)                             # (N*K,)
    order = jnp.argsort(flat_ids, stable=True)                 # sort by expert
    sorted_ids = flat_ids[order]
    # rank within equal-id segment = position - first index of that id
    first = jnp.searchsorted(sorted_ids, sorted_ids, side="left")
    rank_sorted = jnp.arange(N * K) - first
    slots = jnp.zeros((N * K,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))                         # (N*K,)
    keep = slots < C

    token_idx = jnp.repeat(jnp.arange(N), K)                   # (N*K,)
    safe_e = jnp.where(keep, flat_ids, 0)
    safe_s = jnp.where(keep, slots, C - 1)

    buf = jnp.zeros((E, C, D), x.dtype)
    contrib = jnp.where(keep[:, None], x_flat[token_idx], 0.0)
    expert_in = buf.at[safe_e, safe_s].add(contrib)            # (E, C, D)

    # --- expert FFN (vmapped over E; experts sharded over `model`) ---------
    def ffn(w_gate, w_up, w_down, h):
        g = jnp.einsum("cd,df->cf", h, w_gate)
        u = jnp.einsum("cd,df->cf", h, w_up)
        act = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
        return jnp.einsum("cf,fd->cd", act, w_down)

    expert_out = jax.vmap(ffn)(params["w_gate"], params["w_up"],
                               params["w_down"], expert_in)    # (E, C, D)

    # --- combine: gather each assignment's output, weight, and sum over K --
    gathered = expert_out[safe_e, safe_s]                      # (N*K, D)
    w = jnp.where(keep, top_w.reshape(-1), 0.0)                # dropped => 0
    out_flat = jnp.zeros((N, D), jnp.float32).at[token_idx].add(
        gathered.astype(jnp.float32) * w[:, None])
    out = out_flat.astype(x.dtype).reshape(B, T, D)

    aux_total = spec.router_aux_weight * aux + spec.router_z_weight * z
    return out, aux_total
