"""Plain reference of Byzantine gradient descent on the paper's linear
regression (arXiv:1705.05491, Algorithm 2 and Corollary 1), importing
nothing of the program.

One job runs ``rounds`` rounds from theta = 0.  In round t every worker j
reports its full-batch gradient (1/n) X_j^T (X_j theta - y_j); the workers
the round's key picks (a fresh uniformly random q-subset: the lowest q of m
uniform scores drawn from ``fold_in(fold_in(key, t), t)``) report
``-scale`` times it instead; the server averages the reports within k fixed
contiguous batches (the first m mod k batches one worker larger), drops the
batch means whose norm exceeds ``trim_multiplier`` times their median
(Remark 2), takes the geometric median of the rest by Weiszfeld's
iteration from their mean, and steps theta by ``-step_size`` times it.

The workers' gradients are the one pass over the data: they run on the
device, in float32 with every product at ``Precision.HIGHEST``.  The
server's part (attack, batch means, trimming, Weiszfeld, the step) runs in
numpy float64.

``dtype="bfloat16"`` rounds the data, theta and every result to bfloat16
(products accumulated in float32): the lower-precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def _rounder(dtype: str):
    if dtype == "float64":
        return lambda x: np.asarray(x, np.float64)
    if dtype == "bfloat16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown dtype {dtype!r}")


@functools.partial(jax.jit, static_argnames=("low",))
def worker_grads(x, y, theta, low: bool = False):
    """(m, d) gradients of the m workers' mean squared losses at theta;
    ``x`` (m, n, d), ``y`` (m, n).  With ``low`` the operands are bfloat16
    and each result is rounded to bfloat16."""
    dt = jnp.bfloat16 if low else jnp.float32
    n = x.shape[1]
    r = jnp.einsum("mnd,d->mn", x.astype(dt), theta.astype(dt),
                   precision=HIGHEST, preferred_element_type=jnp.float32)
    r = (r - y.astype(dt).astype(jnp.float32)).astype(dt)
    g = jnp.einsum("mn,mnd->md", r, x.astype(dt), precision=HIGHEST,
                   preferred_element_type=jnp.float32) / n
    return g.astype(dt).astype(jnp.float32)


def byzantine_masks(key, rounds: int, workers: int, byzantine: int):
    """(rounds, workers) bools: round t's Byzantine workers."""
    out = np.zeros((rounds, workers), bool)
    if byzantine == 0:
        return out
    for t in range(rounds):
        kt = jax.random.fold_in(jax.random.fold_in(key, t), t)
        u = np.asarray(jax.random.uniform(kt, (workers,)))
        rank = np.argsort(np.argsort(u, kind="stable"), kind="stable")
        out[t] = rank < byzantine
    return out


def batch_slices(workers: int, batches: int):
    base, rem = divmod(workers, batches)
    sizes = [base + 1 if l < rem else base for l in range(batches)]
    starts = np.cumsum([0] + sizes)
    return [slice(int(a), int(b)) for a, b in zip(starts[:-1], starts[1:])]


def geometric_median(z, rnd, *, trim_multiplier, max_iters: int, tol: float,
                     eps: float = 1e-12):
    """Weiszfeld's geometric median of the rows of ``z`` (k, d)."""
    k = z.shape[0]
    norms = rnd(np.sqrt(rnd(np.sum(z * z, axis=1))))
    w = np.ones((k,))
    if trim_multiplier is not None:
        w = (norms <= trim_multiplier * np.median(norms) + eps).astype(float)
        if w.sum() == 0:
            w = np.ones((k,))
    y = rnd(w @ z / max(w.sum(), eps))
    for _ in range(max_iters):
        d = rnd(np.sqrt(rnd(np.sum(rnd(z - y) ** 2, axis=1)) + eps * eps))
        inv = rnd(w / d)
        new = rnd(rnd(inv / max(inv.sum(), eps)) @ z)
        delta = float(np.sum((new - y) ** 2))
        y = new
        if delta <= tol * tol:
            break
    return y


def gd_job(features, targets, key, *, rounds: int, byzantine: int,
           batches: int, attack_scale: float, step_size: float,
           trim_multiplier, max_iters: int, tol: float,
           dtype: str = "float64", masks=None):
    """The job's final theta.  ``features`` (m, n, d) and ``targets`` (m, n)
    are device arrays."""
    rnd = _rounder(dtype)
    low = dtype == "bfloat16"
    m, _, d = features.shape
    if masks is None:
        masks = byzantine_masks(key, rounds, m, byzantine)
    slices = batch_slices(m, batches)
    theta = np.zeros((d,))
    for t in range(rounds):
        g = np.asarray(worker_grads(features, targets,
                                    jnp.asarray(theta, jnp.float32), low=low),
                       np.float64)
        g = np.where(masks[t][:, None], rnd(-attack_scale * g), g)
        z = rnd(np.stack([g[s].sum(axis=0) / (s.stop - s.start)
                          for s in slices]))
        agg = geometric_median(z, rnd, trim_multiplier=trim_multiplier,
                               max_iters=max_iters, tol=tol)
        theta = rnd(theta - step_size * agg)
    return theta
