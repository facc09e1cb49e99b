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

The DeepSeek-V3 layer (``deepseek_*``, arXiv:2412.19437 §2.1.2) is apart
from that path: sigmoid scores, a selection bias that picks the experts but
never weighs them, the chosen weights normalised and scaled, shared experts
on every token, the sequence-wise balance loss, and a dropless dispatch
over the experts this chip holds.  Its router scores every expert of the
layer; only the held experts' part of the result is computed, as expert
parallelism computes it on each chip.
"""

from __future__ import annotations

import dataclasses
import functools

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


# ---------------------------------------------------------------------------
# DeepSeek-V3 expert layer

@dataclasses.dataclass(frozen=True)
class DeepSeekMoESpec:
    d_model: int
    d_ff: int                  # width of one routed expert
    num_experts: int           # the router's outputs: every expert
    experts_per_token: int
    num_shared: int            # shared experts, one SwiGLU num_shared*d_ff wide
    routed_scaling: float
    balance_alpha: float       # weight of the sequence-wise balance loss
    held: int                  # this chip holds experts [held_lo, held_lo+held)
    held_lo: int = 0


def deepseek_init(key, spec: DeepSeekMoESpec, *, dtype):
    k_router, k_gate, k_up, k_down, k_shared = jax.random.split(key, 5)
    E, D, F = spec.held, spec.d_model, spec.d_ff

    def expert_init(k, d_in, d_out):
        return layers.truncated_normal_init(
            k, (E, d_in, d_out), d_in ** -0.5, dtype)

    return {
        "router": layers.dense_init(k_router, D, spec.num_experts,
                                    dtype=dtype),
        # DeepSeek-V3's e_score_correction_bias: set by a load rule outside
        # the gradient (none here), so no gradient reaches it
        "router_bias": jnp.zeros((spec.num_experts,), jnp.float32),
        "experts": {"w_gate": expert_init(k_gate, D, F),
                    "w_up": expert_init(k_up, D, F),
                    "w_down": expert_init(k_down, F, D)},
        "shared": layers.swiglu_init(k_shared, D, spec.num_shared * F,
                                     dtype=dtype),
    }


def deepseek_route(params, spec: DeepSeekMoESpec, x):
    """x: (B, T, D) -> (expert ids (B*T, K), weights (B*T, K) f32, balance
    loss).  The ids are the top-k of score + bias, the weights the chosen
    scores normalised over the k and scaled.  The balance loss is
    ``alpha * sum_i f_i P_i`` per sequence (eqs. 17-20), averaged over the
    sequences: f_i the share of the sequence's top-k picks of the plain
    scores on expert i, times E / K; P_i the mean of its scores normalised
    over the experts."""
    B, T, D = x.shape
    E, K = spec.num_experts, spec.experts_per_token
    logits = jnp.einsum("nd,de->ne", x.reshape(B * T, D).astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    scores = jax.nn.sigmoid(logits)                            # (N, E)
    bias = jax.lax.stop_gradient(params["router_bias"].astype(jnp.float32))
    _, ids = jax.lax.top_k(scores + bias, K)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * spec.routed_scaling

    _, plain = jax.lax.top_k(scores, K)
    picks = jnp.sum(jax.nn.one_hot(plain, E, dtype=jnp.float32), axis=1)
    f = jnp.mean(picks.reshape(B, T, E), axis=1) * (E / K)     # (B, E)
    p = jnp.mean((scores / jnp.sum(scores, axis=-1, keepdims=True))
                 .reshape(B, T, E), axis=1)
    balance = spec.balance_alpha * jnp.mean(jnp.sum(f * p, axis=-1))
    return ids, w, balance


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _take_rows(x, idx, mask, back_idx, back_mask, fold):
    """``where(mask, x[idx], 0)``, whose gradient is again a gather:
    ``x``'s row i takes the rows ``back_idx[j * len(x) + i]``, j < fold, of
    the cotangent where ``back_mask``.  Exact where these list, for each row
    of ``x``, the masked rows that read it (a permutation and its inverse),
    so that neither direction needs a scatter."""
    return jnp.where(mask[:, None], x[idx], 0).astype(x.dtype)


def _take_rows_fwd(x, idx, mask, back_idx, back_mask, fold):
    return (_take_rows(x, idx, mask, back_idx, back_mask, fold),
            (back_idx, back_mask))


def _take_rows_bwd(fold, res, g):
    back_idx, back_mask = res
    gx = jnp.where(back_mask[:, None], g[back_idx], 0)
    gx = jnp.sum(gx.reshape(fold, -1, g.shape[-1]), axis=0).astype(g.dtype)
    return gx, None, None, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _weighted_sum(w, y):
    """``sum_k w[k, :, None] * y[k]`` in f32: w (K, N) f32, y (K, N, D).
    Its gradient writes y's cotangent in y's dtype and reduces w's, one k
    at a time; autodiff's holds an f32 (K, N, D) broadcast of the output's
    cotangent."""
    return jnp.sum(w[:, :, None] * y.astype(jnp.float32), axis=0)


def _weighted_sum_fwd(w, y):
    return _weighted_sum(w, y), (w, y)


def _weighted_sum_bwd(res, g):
    w, y = res
    # one k at a time: a (K, N, D) broadcast of g would be materialised
    gw = jnp.stack([jnp.sum(g * y[k].astype(jnp.float32), axis=-1)
                    for k in range(y.shape[0])])
    gy = jnp.stack([(w[k][:, None] * g).astype(y.dtype)
                    for k in range(y.shape[0])])
    return gw, gy


_weighted_sum.defvjp(_weighted_sum_fwd, _weighted_sum_bwd)


def _sort_by_expert(spec: DeepSeekMoESpec, ids):
    """The assignments (k, token), flattened k-major (assignment k*N + t),
    sorted by held expert, the others after them.  Returns ``(order, pos,
    held, loads)``: ``order[a]`` the assignment at sorted row a, ``pos`` its
    inverse, ``held`` whether an assignment's expert is held here,
    ``loads`` (held,) int32 the rows of each held expert."""
    n_assign = ids.size
    local = ids.T.reshape(-1) - spec.held_lo
    held = (local >= 0) & (local < spec.held)
    bucket = jnp.where(held, local, spec.held)
    order = jnp.argsort(bucket, stable=True).astype(jnp.int32)
    pos = jnp.zeros((n_assign,), jnp.int32).at[order].set(
        jnp.arange(n_assign, dtype=jnp.int32))
    loads = jnp.sum(
        (bucket[:, None] == jnp.arange(spec.held)[None]).astype(jnp.int32),
        axis=0)
    return order, pos, held, loads


def deepseek_apply(params, spec: DeepSeekMoESpec, x):
    """x: (B, T, D) -> (out (B, T, D), balance loss, loads (held,) int32).

    Dropless: every assignment to a held expert is computed.  The buffer
    holds ``B*T*min(K, held)`` rows, as many as the held experts can ever
    receive; the rows past the held assignments are padding, outside every
    group of the grouped matmuls, which skip them.  Scopes: ``router``,
    ``dispatch`` (sort, permute, unpermute), ``experts`` (the grouped
    matmuls), ``shared_experts``."""
    B, T, D = x.shape
    N, K = B * T, spec.experts_per_token
    rows = N * min(K, spec.held)
    x_flat = x.reshape(N, D)
    with jax.named_scope("router"):
        ids, w, balance = deepseek_route(params, spec, x)
    with jax.named_scope("dispatch"):
        order, pos, held, loads = _sort_by_expert(spec, ids)
        order = order[:rows]
        row_held = held[order]
        slot = jnp.minimum(pos, rows - 1)
        x_sorted = _take_rows(x_flat, order % N, row_held, slot, held, K)
    with jax.named_scope("experts"):
        ex = params["experts"]
        gate = jax.lax.ragged_dot(x_sorted, ex["w_gate"], loads)
        up = jax.lax.ragged_dot(x_sorted, ex["w_up"], loads)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
        y_sorted = jax.lax.ragged_dot(act, ex["w_down"], loads)
    with jax.named_scope("dispatch"):
        # k-major rows, so that (K*N, D) -> (K, N, D) moves no data
        y = _take_rows(y_sorted, slot, held, order, row_held, 1)
        w = jnp.where(held.reshape(K, N), w.T, 0.0)
        routed = _weighted_sum(w, y.reshape(K, N, D))
    with jax.named_scope("shared_experts"):
        shared = layers.swiglu(params["shared"], x_flat)
    out = (routed + shared.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, T, D), balance, loads
