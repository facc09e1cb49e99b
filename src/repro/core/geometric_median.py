"""Geometric median of points in R^d — the heart of the paper's aggregator.

The geometric median of ``{z_1..z_n}`` is ``argmin_y sum_i ||y - z_i||_2``
(paper eq. (6)).  The paper invokes the [CLM+16] interior-point solver for a
``(1+gamma)``-approximation; that algorithm is sequential and CPU-bound with
no TPU analogue, so we substitute the classical **Weiszfeld** fixed-point
iteration (see DESIGN.md §3): each step is a batch of distance reductions and
a weighted mean — exactly the VPU/MXU-friendly shape — and converges linearly
to any required tolerance on non-collinear inputs.

All entry points are pure-functional and jit/pjit friendly (``lax.while_loop``
/ ``lax.fori_loop`` only, no Python control flow on traced values).  Points
may live on a sharded mesh: every reduction is a plain ``jnp`` reduction so
GSPMD inserts the cross-device psums.

Supports optional per-point weights so that norm-trimmed points (paper
Remark 2) participate with weight zero without changing static shapes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

# repro: bit-stable — the pytree Weiszfeld is part of the shard-local
# bit-equality contract (tests/test_shardmap_aggregate.py): reductions over
# the stacked k/member axis must stay unrolled multiply-add chains
# (_wsum) or route through blocked_partial_sum (repro.verify RV101/RV105).


class WeiszfeldState(NamedTuple):
    y: jax.Array          # current estimate, shape (d,) or pytree-flattened
    objective: jax.Array  # sum_i w_i ||y - z_i||  (scalar)
    step: jax.Array       # iteration counter (int32)
    delta: jax.Array      # last movement ||y_t - y_{t-1}||


def _pairwise_dists(points: jax.Array, y: jax.Array, eps: float) -> jax.Array:
    """||z_i - y|| for each row of ``points`` (n, d) vs ``y`` (d,).  Smoothed
    by ``eps`` to keep the Weiszfeld weights finite when ``y`` hits a point
    (the standard smoothing; bias is O(eps))."""
    diff = points - y[None, :]
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1) + eps * eps)


def weiszfeld_step(points: jax.Array, y: jax.Array, weights: jax.Array,
                   eps: float) -> jax.Array:
    """One Weiszfeld update: y <- sum_i (w_i/d_i) z_i / sum_i (w_i/d_i)."""
    d = _pairwise_dists(points, y, eps)           # (n,)
    inv = weights / d                             # (n,)
    denom = jnp.sum(inv)
    return (inv @ points) / jnp.maximum(denom, eps)


def geometric_median(points: jax.Array,
                     *,
                     weights: jax.Array | None = None,
                     max_iters: int = 64,
                     tol: float = 1e-8,
                     eps: float = 1e-12) -> jax.Array:
    """(1+gamma)-approximate geometric median of ``points`` (n, d).

    ``tol`` is the movement stopping criterion; with the paper's choice
    gamma = 1/N one sets ``tol ~ objective_scale / N`` — in practice 64
    iterations reach float32 fixed point for the k <= 64 regimes used here.

    Initialization is the weighted mean (the k=1 aggregate), which also makes
    the function exactly reduce to the mean after 0 iterations when n == 1.
    """
    n = points.shape[0]
    if weights is None:
        weights = jnp.ones((n,), dtype=points.dtype)
    weights = weights.astype(points.dtype)

    w_sum = jnp.maximum(jnp.sum(weights), eps)
    y0 = (weights @ points) / w_sum

    def objective(y):
        return jnp.sum(weights * _pairwise_dists(points, y, eps))

    def cond(state: WeiszfeldState):
        return jnp.logical_and(state.step < max_iters, state.delta > tol)

    def body(state: WeiszfeldState):
        y_new = weiszfeld_step(points, state.y, weights, eps)
        return WeiszfeldState(
            y=y_new,
            objective=objective(y_new),
            step=state.step + 1,
            delta=jnp.linalg.norm(y_new - state.y),
        )

    init = WeiszfeldState(y=y0, objective=objective(y0),
                          step=jnp.zeros((), jnp.int32),
                          delta=jnp.array(jnp.inf, points.dtype))
    final = jax.lax.while_loop(cond, body, init)
    return final.y


def geometric_median_pytree(batch_means, *,
                            weights: jax.Array | None = None,
                            max_iters: int = 64,
                            tol: float = 1e-8,
                            eps: float = 1e-12,
                            shard_spec=None,
                            info: dict | None = None):
    """Geometric median of k *pytrees* (paper-faithful "global" mode).

    ``batch_means`` is a pytree whose leaves have a leading axis k (the batch
    means, stacked).  The geometric median treats the concatenation of all
    leaves as one R^d vector: distances are summed across leaves via plain
    jnp reductions (=> psum across the model axis when leaves are sharded);
    **no leaf is ever gathered or flattened**, so the peak memory per device
    stays at k × (its shard of the model).

    ``shard_spec`` (a :class:`repro.core.shard_aggregation.ShardSpec`)
    selects the shard-local contract: the Weiszfeld iterate and every
    weighted mean stay per-shard (the weighted k-sums are coordinate-local
    and bitwise width-invariant), and only the (k,) squared distances and
    the scalar movement cross shards — ONE small blocked reduction per
    iterate.  With a trivial spec (None / gspmd) the reductions follow the
    legacy accumulation order (golden traces stay within tolerance).

    ``info`` (a dict) receives ``"weiszfeld_iters"``: the loop's final
    counter, the Weiszfeld steps taken (int32, at most ``max_iters``).

    Returns a pytree of the same structure without the leading axis.
    """
    from repro.core.shard_aggregation import blocked_partial_sum

    leaves, treedef = jax.tree.flatten(batch_means)
    k = leaves[0].shape[0]
    if weights is None:
        weights = jnp.ones((k,), dtype=jnp.float32)
    weights = weights.astype(jnp.float32)
    w_sum = jnp.maximum(jnp.sum(weights), eps)

    def _wsum(w, l):
        # weighted sum over the leading k axis as an UNROLLED elementwise
        # multiply-add chain: each output coordinate gets a fixed expression
        # tree, so a shard's slice computes exactly the bits of the full
        # leaf's slice.  Both a dot/tensordot lowering and a fused
        # broadcast-multiply + sum-over-k are width-sensitive (the compiler
        # may reassociate or vectorize the k-reduction differently per
        # coordinate width), which would break the shard-local bit-equality
        # contract; k is small (<= num_workers) so unrolling is cheap.
        wf = w.astype(l.dtype)
        acc = wf[0] * l[0]
        for i in range(1, l.shape[0]):
            acc = acc + wf[i] * l[i]
        return acc

    def wmean(ls):
        return [_wsum(weights, l) / w_sum.astype(l.dtype) for l in ls]

    def _pair_sq(l, yl):
        diff = (l - yl[None]).astype(jnp.float32)
        return jnp.sum(diff * diff, axis=tuple(range(1, diff.ndim)))

    def sq_dists(ls, y):
        """(k,) squared distances from stacked points to estimate y."""
        return blocked_partial_sum(shard_spec, list(zip(ls, y)), _pair_sq,
                                   shape=(k,), lead_axes=1)

    def step(y):
        d = jnp.sqrt(sq_dists(leaves, y) + eps * eps)        # (k,)
        inv = weights / d
        denom = jnp.maximum(jnp.sum(inv), eps)
        y_new = [_wsum(inv / denom, l) for l in leaves]
        return y_new

    y0 = wmean(leaves)

    def _pair_delta(x, z):
        return jnp.sum((x - z).astype(jnp.float32) ** 2)

    def flat_delta(a, b):
        return blocked_partial_sum(shard_spec, list(zip(a, b)), _pair_delta,
                                   shape=(), lead_axes=0)

    def cond(carry):
        _, it, delta = carry
        return jnp.logical_and(it < max_iters, delta > tol * tol)

    def body(carry):
        y, it, _ = carry
        y_new = step(y)
        return (y_new, it + 1, flat_delta(y_new, y))

    y, it, _ = jax.lax.while_loop(
        cond, body, (y0, jnp.zeros((), jnp.int32),
                     jnp.array(jnp.inf, jnp.float32)))
    if info is not None:
        info["weiszfeld_iters"] = it
    return jax.tree.unflatten(treedef, y)


def trim_weights(norms: jax.Array, *, multiplier: float = 3.0,
                 eps: float = 1e-12) -> jax.Array:
    """Norm-trimming weights (paper Remark 2, self-tuning threshold).

    The paper trims batch means with norm > tau = Theta(d) before the
    approximate geomed so the gamma-deviation term (prop. to max_i ||z_i||)
    stays bounded.  A fixed Theta(d) constant is analysis-only; we use the
    robust, scale-free tau = multiplier × median(norms): at least half the
    batches are honest (k >= 2(1+eps)q), so the median norm is within the
    honest envelope and honest batches are kept w.h.p.

    Returns {0,1} weights, guaranteed not all zero.
    """
    tau = multiplier * jnp.median(norms) + eps
    w = (norms <= tau).astype(norms.dtype)
    # Degenerate guard: if everything got trimmed (all-equal huge norms),
    # fall back to uniform weights rather than a 0/0.
    return jnp.where(jnp.sum(w) > 0, w, jnp.ones_like(w))


def batch_mean_norms(batch_means, *, shard_spec=None) -> jax.Array:
    """Global L2 norm of each of the k stacked pytree batch means.

    With a blocked ``shard_spec`` the squared norms are accumulated as
    per-shard partials and combined by one ordered (k,)-sized reduction —
    the only collective a norm-based selection rule needs."""
    from repro.core.shard_aggregation import blocked_partial_sum

    leaves = jax.tree.leaves(batch_means)
    k = leaves[0].shape[0]

    def _leaf_sq(l):
        lf = l.astype(jnp.float32)
        return jnp.sum(lf * lf, axis=tuple(range(1, lf.ndim)))

    return jnp.sqrt(blocked_partial_sum(shard_spec, leaves, _leaf_sq,
                                        shape=(k,), lead_axes=1))


@functools.partial(jax.jit, static_argnames=("max_iters",))
def geometric_median_jit(points, *, max_iters: int = 64):
    return geometric_median(points, max_iters=max_iters)
