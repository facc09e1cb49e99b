"""Geometric median of points in R^d — the heart of the paper's aggregator.

The geometric median of ``{z_1..z_n}`` is ``argmin_y sum_i ||y - z_i||_2``
(paper eq. (6)).  The paper invokes the [CLM+16] interior-point solver for a
``(1+gamma)``-approximation; that algorithm is sequential and CPU-bound with
no TPU analogue, so we substitute the classical **Weiszfeld** fixed-point
iteration (see DESIGN.md §3), which converges linearly to any required
tolerance on non-collinear inputs.

Two forms of the same iteration:

* :func:`geometric_median` — the point form on an (n, d) f32 array: each
  step reduces the n distances to the iterate and forms a weighted mean.
* :func:`geometric_median_pytree` — the form every aggregator runs on the
  stacked reports (a pytree with a leading k axis).  Every iterate is a
  convex combination of the k points, so the loop iterates on the f32
  coefficients over the k×k Gram matrix ``X Xᵀ``: one pass over the points
  builds it, the loop touches nothing of size d, and one more pass forms
  the median.  Under the shard-local contract the Gram matrix is the only
  cross-shard reduction.

All entry points are pure-functional and jit/pjit friendly (``lax.while_loop``
/ ``lax.fori_loop`` only, no Python control flow on traced values).

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


#: the largest k whose Gram matrix is built as k(k+1)/2 fused scalar
#: reductions; above it, as one dot_general (the crossover on a TPU v5e
#: lies between k = 8 and k = 12, see ``geometric_median_pytree``)
GRAM_PAIRS_MAX_K = 8


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


def weighted_sum(w: jax.Array, l: jax.Array) -> jax.Array:
    """``Σ_i w_i l[i]`` over the leading k axis of ``l``, in ``l``'s dtype."""
    # an UNROLLED elementwise multiply-add chain: each output coordinate gets a fixed expression
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
    leaves as one R^d vector; **no leaf is ever gathered, flattened or copied
    to f32**, so the peak memory per device stays at k × (its shard of the
    model).

    Every Weiszfeld iterate is a convex combination ``y = Σ_j c_j x_j`` of
    the k points (the start is the weighted mean, and each update
    reweights the points), so the loop runs on the f32 coefficients ``c``
    over the k×k Gram matrix ``G = X Xᵀ`` instead of on a d-sized iterate:

    * ``gram``    — one pass over the points builds ``G`` (f32 accumulation
      of the points' products, per leaf);
    * the loop    — ``‖x_i − y‖² = G_ii − 2(Gc)_i + cᵀGc`` and the movement
      ``‖y' − y‖² = (c' − c)ᵀ G (c' − c)``, both on k×k numbers;
    * ``combine`` — one more pass forms ``y = Σ_j c_j x_j`` in the points'
      dtype.

    The stopping rule (squared movement <= ``tol²`` or ``max_iters``) and
    the ``eps`` smoothing are the point form's (:func:`geometric_median`).

    ``shard_spec`` (a :class:`repro.core.shard_aggregation.ShardSpec`)
    selects the shard-local contract: ``G`` is accumulated from per-shard
    partials through ONE (k, k) blocked reduction, the only collective;
    the loop runs on the replicated ``G`` and the combine is
    coordinate-local (bitwise width-invariant).

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

    def _leaf_gram(l):
        # X Xᵀ over every coordinate dim, from exact products of the points
        # accumulated in f32, reading each leaf once
        if k <= GRAM_PAIRS_MAX_K:
            # one scalar multiply-reduce per pair: XLA fuses the siblings
            # into one pass over the leaf.  The TPU lowers the dot below as
            # a convolution: on a v5e, one (k, 4096, 14336) bf16 leaf takes
            # 1.2 ms this way against the dot's 3.3 ms at k = 4, 3.2 against
            # 3.6 ms at k = 8, and 9.4 against 7.2 ms at k = 12 (PERF.md)
            g = [[None] * k for _ in range(k)]
            for i in range(k):
                for j in range(i, k):
                    g[i][j] = g[j][i] = jnp.sum(l[i].astype(jnp.float32)
                                                * l[j].astype(jnp.float32))
            return jnp.stack([jnp.stack(row) for row in g])
        # past it the k(k+1)/2 reductions cost more than one matrix-unit
        # contraction (HIGHEST keeps f32 points at f32 on the TPU)
        axes = tuple(range(1, l.ndim))
        return jax.lax.dot_general(
            l, l, dimension_numbers=((axes, axes), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    with jax.named_scope("gram"):
        gram = blocked_partial_sum(shard_spec, leaves, _leaf_gram,
                                   shape=(k, k), lead_axes=1)
    diag = jnp.diagonal(gram)

    # the loop's k-reductions as unrolled f32 multiply-add chains: exact f32
    # on every backend (no matrix unit), and a fixed expression tree
    def _gram_times(c):
        acc = gram[:, 0] * c[0]
        for j in range(1, k):
            acc = acc + gram[:, j] * c[j]
        return acc

    def _inner(a, b):
        acc = a[0] * b[0]
        for j in range(1, k):
            acc = acc + a[j] * b[j]
        return acc

    def cond(carry):
        _, it, delta = carry
        return jnp.logical_and(it < max_iters, delta > tol * tol)

    def body(carry):
        c, it, _ = carry
        gc = _gram_times(c)
        sq = jnp.maximum(diag - 2.0 * gc + _inner(c, gc), 0.0)
        inv = weights / jnp.sqrt(sq + eps * eps)
        c_new = inv / jnp.maximum(jnp.sum(inv), eps)
        dc = c_new - c
        return (c_new, it + 1,
                jnp.maximum(_inner(dc, _gram_times(dc)), 0.0))

    c, it, _ = jax.lax.while_loop(
        cond, body, (weights / w_sum, jnp.zeros((), jnp.int32),
                     jnp.array(jnp.inf, jnp.float32)))
    if info is not None:
        info["weiszfeld_iters"] = it
    with jax.named_scope("combine"):
        y = [weighted_sum(c, l) for l in leaves]
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
