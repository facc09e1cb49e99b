"""Fused Pallas TPU round kernel: grads -> batch means -> Weiszfeld, one pass.

The server's per-round hot path (paper Algorithm 2, steps 1-4) was three
separate HBM-level stages in the scan trainer:

    stacked per-worker gradients G (m, d)
      -> k batch means Z (k, d)          [gather + reshape + mean]
      -> norm trimming weights w (k,)    [one pass over Z]
      -> Weiszfeld loop on Z             [2-3 passes over Z per iteration]

This module fuses the whole thing into ONE kernel invocation:

  * G is streamed tile-by-tile (m, TILE_D) — a single HBM read of the
    stacked gradients;
  * batch means are a (k, m) x (m, TILE_D) matmul against the grouping's
    dense membership matrix (``core.grouping.assignment_matrix``), so any
    grouping scheme — contiguous / strided / seeded, even or uneven batch
    sizes — is the same MXU contraction;
  * the (k, d) batch-mean block Z is accumulated into a VMEM scratch, and
    the trim weights (paper Remark 2) AND the full Weiszfeld fixed-point
    loop run on that buffer without touching HBM again; only the final
    aggregate y (d,) is written back.

VMEM budget (``round_resident_bytes``): Z with k padded to whole sublane
tiles, the iterate output (counted as 8 sublanes and double-buffered), and
the double-buffered input tiles, all f32.  At m=50, k=11 that admits d up
to 96256 inside ``VMEM_BUDGET_BYTES``; the production dispatcher
(``core.aggregators.gmom_aggregator``) falls back to the unfused jnp path
above that, so model-scale leaves keep working.  tests/test_tpu_compile.py
compiles the kernel for a described v5e at the largest admitted d.

``round_aggregate_ref`` is the pure-jnp twin that mirrors the kernel's tile
loop and operation order exactly — it is bit-identical to the kernel in
interpret mode (tests/test_round_kernel.py asserts exact equality).

``linreg_round_*`` goes one stage further for the paper's linear-regression
substrate (§4): the kernel receives the RAW worker batches (X, y) and the
current iterate theta, computes every worker's full-batch gradient
(1/n) X_j^T (X_j theta - y_j) in-kernel (two streamed passes over X), and
feeds it straight into the same means+trim+Weiszfeld tail — the entire
round of Algorithm 2 as one kernel.  The v5e compiler refuses it (see its
section below).

The Weiszfeld loop is an early-exiting ``lax.while_loop`` with the same
stopping rule as the unfused jnp path (squared movement <= tol^2, capped at
``max_iters``).  Every pass walks the resident block one (k, TILE_D) chunk
at a time (``_trim_weiszfeld``), so no vector value larger than a chunk is
live and the compiled code does not grow with d.  In-kernel the loops carry
only scalars and (k, 1) columns — the iterate lives in the output ref; the
jnp twin carries the iterate as an ordinary array but computes the
identical values iteration for iteration, which is what makes the pair
bit-identical in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.grouping import Grouping, assignment_matrix

# repro: bit-stable — the kernel/reference pair must stay bit-identical in
# interpret mode (tests/test_round_kernel.py): keep the shared op sequence,
# no jnp.sum/jnp.mean over the member axis outside it (repro.verify RV101).

TILE_D = 512
# The scoped-VMEM limit the TPU compiler enforces on a kernel by default on
# v5e (its refusals quote "limit 16.00M"); compiling past it raises
# RESOURCE_EXHAUSTED.  The budget keeps a quarter of it for the compiler's
# own scratch.  repro.verify's static VMEM audit (RV204) checks
# VMEM_BUDGET_BYTES <= DEVICE_VMEM_BYTES and that the dispatcher's
# fits_vmem() and the kernel's own _check_vmem() guard agree on a shape grid.
DEVICE_VMEM_BYTES = 16 * 2**20
VMEM_BUDGET_BYTES = 12 * 2**20


def default_use_pallas(target_backend: str | None = None) -> bool:
    """Whether the fused Pallas kernel is the default lowering.

    ``target_backend`` names the backend the program will RUN on (threaded
    from a ShardSpec by ``aggregators.resolve_round_backend``); None falls
    back to the live host backend."""
    return (target_backend or jax.default_backend()) == "tpu"


def _pad_axis(x, tile: int, axis: int):
    pad = (-x.shape[axis]) % tile
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# building blocks shared verbatim by the kernel and its jnp reference —
# sharing the exact op sequence is what buys bit-equality in interpret mode.
#
# The resident batch-mean block is held as (n_chunks, k, chunk): chunk c is
# columns [c*chunk, (c+1)*chunk) of the (k, d_pad) block, so every loop
# indexes the untiled leading axis and no value larger than (k, chunk) is
# ever live.  Per-batch quantities are (k, 1) columns: Mosaic cannot
# relayout a (k,) vector into a row or column.

def _median_small(x):
    """``jnp.median`` of a small (k, 1) column without sorting.

    Mosaic has no in-kernel sort; for the k <= 64 trim-weight median we rank
    every element against every other (O(k^2) compares on the VPU, ties
    broken by index so ranks are a permutation) and select the middle order
    statistic(s) by mask.  The row copy of ``x`` is built by masking and
    adding rows, not by a transpose.  Every sum here has exactly one
    nonzero term, so it is exact."""
    k = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    row = _add_rows(jnp.where(ii == jj, x, jnp.zeros((k, k), x.dtype)))
    less = (row < x) | ((row == x) & (jj < ii))
    rank = jnp.sum(less.astype(jnp.int32), axis=1, keepdims=True)   # (k, 1)

    def order_stat(r):
        return jnp.sum(jnp.where(rank == r, x, jnp.zeros_like(x)))

    if k % 2 == 1:
        return order_stat(k // 2)
    return 0.5 * (order_stat(k // 2 - 1) + order_stat(k // 2))


def _trim_weights(sq_norms, trim_multiplier):
    """Paper Remark-2 trim weights, a (k, 1) column, from the squared norms
    of the batch means."""
    norms = jnp.sqrt(sq_norms)
    tau = trim_multiplier * _median_small(norms) + 1e-12
    w = (norms <= tau).astype(jnp.float32)
    return jnp.where(jnp.sum(w) > 0, w, jnp.ones_like(w))


def _row_sq(x):
    """Per-row sum of squares of a (k, chunk) slab: (k, 1)."""
    return jnp.sum(x * x, axis=1, keepdims=True)


def _add_rows(x):
    """Sum of the rows of a (k, n) value as an unrolled add chain: (1, n).
    The fixed order keeps the kernel and its jnp twin bit-identical (a
    reduction over the member axis may be reassociated per fusion)."""
    acc = x[0:1]
    for l in range(1, x.shape[0]):
        acc = acc + x[l:l + 1]
    return acc


def _weighted_rows(c, x):
    """sum_l c[l] * x[l] for a (k, 1) column c: (1, chunk), on the VPU."""
    return _add_rows(c * x)


def _trim_weiszfeld(z_at, y_get, y_set, y, *, k, n_chunks, trim_multiplier,
                    max_iters, tol, eps):
    """Remark-2 trim + Weiszfeld on the resident block, chunk by chunk.

    ``z_at(c)`` reads chunk c of the batch means, (k, chunk).  The iterate
    is reached through ``y_get(y, c)`` -> (1, chunk) and ``y_set(y, c, v)``
    -> y: in the kernel ``y`` is ``()`` and the iterate lives in the output
    ref, so every loop carries only scalars and (k, 1) columns; in the jnp
    twin ``y`` is an ordinary (n_chunks, 1, chunk) array.  Both run the same
    ops in the same order.  Each iteration makes two passes over the block:
    distances to the old iterate, then the new iterate and its squared
    movement.  Early exit: stop when the squared movement drops to tol^2 or
    after ``max_iters`` steps — the same stopping rule as the unfused jnp
    path."""
    fori = jax.lax.fori_loop
    zero_col = jnp.zeros((k, 1), jnp.float32)
    if trim_multiplier is None:
        w = jnp.ones((k, 1), jnp.float32)
    else:
        sq = fori(0, n_chunks, lambda c, acc: acc + _row_sq(z_at(c)),
                  zero_col)
        w = _trim_weights(sq, trim_multiplier)
    w_sum = jnp.maximum(jnp.sum(w), eps)
    y = fori(0, n_chunks,
             lambda c, y: y_set(y, c, _weighted_rows(w, z_at(c)) / w_sum), y)

    def cond(carry):
        _, it, delta2 = carry
        return jnp.logical_and(it < max_iters, delta2 > tol * tol)

    def body(carry):
        y, it, _ = carry
        sq = fori(0, n_chunks,
                  lambda c, acc: acc + _row_sq(z_at(c) - y_get(y, c)),
                  zero_col)
        inv = w / jnp.sqrt(sq + eps * eps)
        coef = inv / jnp.maximum(jnp.sum(inv), eps)

        def update(c, carry):
            y, delta2 = carry
            new = _weighted_rows(coef, z_at(c))
            delta2 = delta2 + jnp.sum((new - y_get(y, c)) ** 2)
            return y_set(y, c, new), delta2

        y, delta2 = fori(0, n_chunks, update,
                         (y, jnp.zeros((), jnp.float32)))
        return y, it + 1, delta2

    y, _, _ = jax.lax.while_loop(
        cond, body, (y, jnp.zeros((), jnp.int32),
                     jnp.array(jnp.inf, jnp.float32)))
    return y


def _finish_in_kernel(z_ref, y_ref, **kw):
    """Kernel tail: trim + Weiszfeld with the iterate in the (n_chunks, 1,
    chunk) output ref."""
    def y_set(_, c, v):
        y_ref[c] = v
        return ()

    n_chunks = z_ref.shape[0]
    _trim_weiszfeld(lambda c: z_ref[c], lambda _, c: y_ref[c], y_set, (),
                    k=z_ref.shape[1], n_chunks=n_chunks, **kw)


def _finish_jnp(z, **kw):
    """jnp twin of ``_finish_in_kernel`` on a (n_chunks, k, chunk) array;
    returns the (d_pad,) aggregate."""
    n_chunks, k, chunk = z.shape
    idx = jax.lax.dynamic_index_in_dim
    y = _trim_weiszfeld(
        lambda c: idx(z, c, keepdims=False),
        lambda y, c: idx(y, c, keepdims=False),
        lambda y, c, v: jax.lax.dynamic_update_index_in_dim(y, v, c, 0),
        jnp.zeros((n_chunks, 1, chunk), jnp.float32),
        k=k, n_chunks=n_chunks, **kw)
    return y.reshape(-1)


def _chunked(z, chunk: int):
    """(k, d) -> (n_chunks, k, chunk), zero-padding d to a chunk multiple."""
    z = _pad_axis(z, chunk, 1)
    k, d_pad = z.shape
    return z.reshape(k, d_pad // chunk, chunk).transpose(1, 0, 2)


# ---------------------------------------------------------------------------
# kernel 1: stacked gradients -> aggregate   (the scan trainer's hot path)

def _round_kernel(g_ref, s_ref, bsz_ref, y_ref, z_ref, *, n_tiles, **kw):
    """Grid over d-tiles; z_ref is the VMEM scratch (n_tiles, k, tile_d) that
    accumulates the batch means, one tile per grid step."""
    i = pl.program_id(0)
    sums = jnp.dot(s_ref[...], g_ref[...],
                   preferred_element_type=jnp.float32)      # (k, tile_d)
    z_ref[i] = sums / bsz_ref[...]

    @pl.when(i == n_tiles - 1)
    def _finish():
        _finish_in_kernel(z_ref, y_ref, **kw)


def _resident_bytes(k: int, d_pad: int, extra_bytes: int) -> int:
    """VMEM the fused kernels hold beyond their streamed tiles: the batch
    means with k padded to whole (8, 128) sublane tiles, plus the iterate
    output (n_chunks, 1, chunk), whose every chunk also fills a whole
    (8, chunk) tile and which Pallas double-buffers."""
    k_pad = -(-k // 8) * 8
    return (k_pad + 2 * 8) * d_pad * 4 + extra_bytes


def _tile_bytes(m: int, k: int, tile_d: int) -> int:
    """The double-buffered input blocks of ``round_aggregate_kernel``: one
    (m, tile_d) gradient tile, the (k, m) membership matrix and the (k, 1)
    batch sizes, each padded to (8, 128) tiles."""
    def padded(rows, cols):
        return (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * 4
    return 2 * (padded(m, tile_d) + padded(k, m) + padded(k, 1))


def round_resident_bytes(m: int, k: int, d: int,
                         tile_d: int = TILE_D) -> int:
    """VMEM footprint of ``round_aggregate_kernel``.  The dispatcher
    (``core.aggregators.resolve_round_backend``) and the kernel's own guard
    use this same formula, so 'auto' never dispatches a shape the kernel
    would reject."""
    d_pad = -(-d // tile_d) * tile_d
    return _resident_bytes(k, d_pad, _tile_bytes(m, k, tile_d))


def fits_vmem(m: int, k: int, d: int, tile_d: int = TILE_D) -> bool:
    return round_resident_bytes(m, k, d, tile_d) <= VMEM_BUDGET_BYTES


def _check_vmem(k: int, d_pad: int, extra_bytes: int = 0):
    resident = _resident_bytes(k, d_pad, extra_bytes)
    if resident > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"fused round kernel resident set {resident} B (k={k}, "
            f"d_pad={d_pad}) exceeds VMEM budget {VMEM_BUDGET_BYTES} B; "
            "use the unfused jnp path (round_backend='reference')")


@functools.partial(jax.jit, static_argnames=(
    "grouping", "trim_multiplier", "max_iters", "tol", "eps", "tile_d",
    "interpret"))
def round_aggregate_kernel(stacked_grads, grouping: Grouping, *,
                           trim_multiplier: float | None = 3.0,
                           max_iters: int = 64, tol: float = 1e-8,
                           eps: float = 1e-12, tile_d: int = TILE_D,
                           interpret: bool = False):
    """Fused GMoM round: stacked (m, d) gradients -> (d,) aggregate.

    One HBM read of the stacked gradients; batch means, Remark-2 trimming,
    and the entire Weiszfeld loop happen on the VMEM-resident (k, d) block.
    Bit-identical to ``round_aggregate_ref`` in interpret mode.
    """
    m, d = stacked_grads.shape
    k = grouping.num_batches
    g = _pad_axis(stacked_grads.astype(jnp.float32), tile_d, 1)
    d_pad = g.shape[1]
    n_tiles = d_pad // tile_d
    _check_vmem(k, d_pad, extra_bytes=_tile_bytes(m, k, tile_d))
    s = jnp.asarray(assignment_matrix(grouping))
    bsz = jnp.asarray(grouping.batch_sizes, jnp.float32).reshape(k, 1)

    y = pl.pallas_call(
        functools.partial(_round_kernel, n_tiles=n_tiles,
                          trim_multiplier=trim_multiplier,
                          max_iters=max_iters, tol=tol, eps=eps),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((m, tile_d), lambda i: (0, i)),
            pl.BlockSpec((k, m), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n_tiles, 1, tile_d), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, tile_d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_tiles, k, tile_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(g, s, bsz)
    return y.reshape(-1)[:d]


@functools.partial(jax.jit, static_argnames=(
    "grouping", "trim_multiplier", "max_iters", "tol", "eps", "tile_d"))
def round_aggregate_ref(stacked_grads, grouping: Grouping, *,
                        trim_multiplier: float | None = 3.0,
                        max_iters: int = 64, tol: float = 1e-8,
                        eps: float = 1e-12, tile_d: int = TILE_D):
    """jnp twin of ``round_aggregate_kernel``: same ops, same reductions.

    The bit-exact oracle for the kernel in interpret mode.  The means are
    one (k, m) x (m, tile_d) dot per d-tile, as in the kernel's grid: a
    single flat dot may block its sum over the workers differently, which
    costs the last bit (tests/test_round_kernel.py asserts exact equality).
    """
    m, d = stacked_grads.shape
    k = grouping.num_batches
    g = _pad_axis(stacked_grads.astype(jnp.float32), tile_d, 1)
    n_tiles = g.shape[1] // tile_d
    s = jnp.asarray(assignment_matrix(grouping))
    bsz = jnp.asarray(grouping.batch_sizes, jnp.float32).reshape(k, 1)
    tiles = g.reshape(m, n_tiles, tile_d).transpose(1, 0, 2)
    z = jax.lax.map(
        lambda gt: jnp.dot(s, gt, preferred_element_type=jnp.float32) / bsz,
        tiles)                                          # (n_tiles, k, tile_d)
    y = _finish_jnp(z, trim_multiplier=trim_multiplier, max_iters=max_iters,
                    tol=tol, eps=eps)
    return y[:d]


def round_aggregate_pytree(stacked_grads, grouping: Grouping, *,
                           trim_multiplier: float | None = 3.0,
                           max_iters: int = 64, tol: float = 1e-8,
                           eps: float = 1e-12, tile_d: int = TILE_D,
                           use_pallas: bool | None = None,
                           interpret: bool = False):
    """Pytree front door: stacked (m, ...) gradient pytree -> aggregate.

    Leaves are flattened and concatenated into one (m, D) f32 block (the
    geometric median is taken in the concatenated R^D, exactly like
    ``core.geometric_median_pytree``) and the result is split back, cast to
    each leaf's dtype.  Compute is f32 throughout.  The round itself runs
    under the ``round_kernel`` named scope.
    """
    leaves, treedef = jax.tree.flatten(stacked_grads)
    m = leaves[0].shape[0]
    flat = [l.reshape(m, -1).astype(jnp.float32) for l in leaves]
    block = flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=1)
    use_pallas = default_use_pallas() if use_pallas is None else use_pallas
    fn = (round_aggregate_kernel if (use_pallas or interpret)
          else round_aggregate_ref)
    kwargs = dict(trim_multiplier=trim_multiplier, max_iters=max_iters,
                  tol=tol, eps=eps, tile_d=tile_d)
    if use_pallas or interpret:
        kwargs["interpret"] = interpret
    with jax.named_scope("round_kernel"):
        y = fn(block, grouping, **kwargs)
    out, offset = [], 0
    for l in leaves:
        size = int(np.prod(l.shape[1:], dtype=np.int64)) if l.ndim > 1 else 1
        piece = jax.lax.slice_in_dim(y, offset, offset + size, axis=0)
        out.append(piece.reshape(l.shape[1:]).astype(l.dtype))
        offset += size
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# kernel 2: raw linreg batches -> aggregate  (the whole round in-kernel)
#
# The v5e compiler refuses this kernel: its batched (m, n) x (m, n, tile_d)
# dot_general has no TPU dot-dimension encoding.  Nothing on the production
# path calls it (ROADMAP, Queue 3); its interpret-mode test keeps the tail it
# shares with kernel 1 honest.

def _linreg_round_kernel(x_ref, t_ref, theta_ref, s_ref, bsz_ref,
                         y_ref, r_ref, z_ref, *, n_tiles, inv_n, **kw):
    """Grid (2, n_tiles).  Phase 0 streams X to build the residual
    R = X @ theta - y (resident, (m, n)); phase 1 streams X again to form
    each worker's gradient tile (1/n) X^T R, contracts it with the
    membership matrix into the resident batch means, and finishes with the
    same trim + Weiszfeld tail as the gradient-input kernel.  X is read
    twice and nothing else touches HBM."""
    phase = pl.program_id(0)
    i = pl.program_id(1)
    x = x_ref[...]                                     # (m, n, tile_d)
    theta_t = theta_ref[...]                           # (1, tile_d)
    tile_d = x.shape[2]

    @pl.when(phase == 0)
    def _residual():
        @pl.when(i == 0)
        def _init():
            r_ref[...] = -t_ref[...]
        # R += X[:, :, tile] @ theta[tile]
        part = jax.lax.dot_general(
            x, theta_t.reshape(tile_d, 1),
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (m, n, 1)
        r_ref[...] += part[..., 0]

    @pl.when(phase == 1)
    def _grads_means():
        r = r_ref[...]                                 # (m, n)
        # worker gradients for this tile: (1/n) X_j^T r_j, all j at once
        g = jax.lax.dot_general(
            r, x, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * inv_n  # (m, tile_d)
        sums = jnp.dot(s_ref[...], g,
                       preferred_element_type=jnp.float32)
        z_ref[i] = sums / bsz_ref[...]

        @pl.when(i == n_tiles - 1)
        def _finish():
            _finish_in_kernel(z_ref, y_ref, **kw)


@functools.partial(jax.jit, static_argnames=(
    "grouping", "trim_multiplier", "max_iters", "tol", "eps", "tile_d",
    "interpret"))
def linreg_round_kernel(features, targets, theta, grouping: Grouping, *,
                        trim_multiplier: float | None = 3.0,
                        max_iters: int = 64, tol: float = 1e-8,
                        eps: float = 1e-12, tile_d: int = 256,
                        interpret: bool = False):
    """One FULL failure-free round of Algorithm 2 on the linreg substrate:
    (X (m, n, d), y (m, n), theta (d,)) -> robust aggregate gradient (d,).

    The per-worker full-batch gradients (1/n) X_j^T (X_j theta - y_j) are
    computed in-kernel — the raw batches never materialize a gradient,
    batch-mean, or distance tensor in HBM.
    """
    m, n, d = features.shape
    k = grouping.num_batches
    x = _pad_axis(features.astype(jnp.float32), tile_d, 2)
    d_pad = x.shape[2]
    n_tiles = d_pad // tile_d
    _check_vmem(k, d_pad,
                extra_bytes=(m * n * tile_d + m * n + k * m) * 4)
    theta_p = _pad_axis(theta.astype(jnp.float32).reshape(1, d), tile_d, 1)
    s = jnp.asarray(assignment_matrix(grouping))
    bsz = jnp.asarray(grouping.batch_sizes, jnp.float32).reshape(k, 1)

    y, _ = pl.pallas_call(
        functools.partial(_linreg_round_kernel, n_tiles=n_tiles,
                          inv_n=1.0 / n, trim_multiplier=trim_multiplier,
                          max_iters=max_iters, tol=tol, eps=eps),
        grid=(2, n_tiles),
        in_specs=[
            pl.BlockSpec((m, n, tile_d), lambda p, i: (0, 0, i)),
            pl.BlockSpec((m, n), lambda p, i: (0, 0)),
            pl.BlockSpec((1, tile_d), lambda p, i: (0, i)),
            pl.BlockSpec((k, m), lambda p, i: (0, 0)),
            pl.BlockSpec((k, 1), lambda p, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((n_tiles, 1, tile_d), lambda p, i: (0, 0, 0)),
            pl.BlockSpec((m, n), lambda p, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles, 1, tile_d), jnp.float32),
            jax.ShapeDtypeStruct((m, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_tiles, k, tile_d), jnp.float32)],
        interpret=interpret,
    )(x, targets.astype(jnp.float32), theta_p, s, bsz)
    return y.reshape(-1)[:d]


@functools.partial(jax.jit, static_argnames=(
    "grouping", "trim_multiplier", "max_iters", "tol", "eps", "tile_d"))
def linreg_round_ref(features, targets, theta, grouping: Grouping, *,
                     trim_multiplier: float | None = 3.0,
                     max_iters: int = 64, tol: float = 1e-8,
                     eps: float = 1e-12, tile_d: int = 256):
    """jnp twin of ``linreg_round_kernel`` (same tiling and op order): the
    bit-exact interpret-mode oracle.  Unlike the gradient-input case, the
    residual accumulates over d-tiles (the contraction runs over the tiled
    axis), so the mirror must replay the kernel's tile loop and partial-sum
    chaining exactly; benchmarks use ``linreg_round_fused`` — the same
    algorithm without the tile structure — on non-TPU backends."""
    m, n, d = features.shape
    k = grouping.num_batches
    x = _pad_axis(features.astype(jnp.float32), tile_d, 2)
    d_pad = x.shape[2]
    n_tiles = d_pad // tile_d
    theta_p = _pad_axis(theta.astype(jnp.float32).reshape(1, d), tile_d, 1)
    s = jnp.asarray(assignment_matrix(grouping))
    bsz = jnp.asarray(grouping.batch_sizes, jnp.float32).reshape(k, 1)
    inv_n = 1.0 / n

    r = -targets.astype(jnp.float32)
    xt = [jax.lax.slice_in_dim(x, i * tile_d, (i + 1) * tile_d, axis=2)
          for i in range(n_tiles)]
    tt = [jax.lax.slice_in_dim(theta_p, i * tile_d, (i + 1) * tile_d, axis=1)
          for i in range(n_tiles)]
    for i in range(n_tiles):
        part = jax.lax.dot_general(
            xt[i], tt[i].reshape(tile_d, 1),
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        r = r + part[..., 0]
    tiles = []
    for i in range(n_tiles):
        g = jax.lax.dot_general(
            r, xt[i], dimension_numbers=(((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * inv_n
        tiles.append(jnp.dot(s, g, preferred_element_type=jnp.float32)
                     / bsz)
    y = _finish_jnp(jnp.stack(tiles), trim_multiplier=trim_multiplier,
                    max_iters=max_iters, tol=tol, eps=eps)
    return y[:d]


@functools.partial(jax.jit, static_argnames=(
    "grouping", "trim_multiplier", "max_iters", "tol", "eps"))
def linreg_round_fused(features, targets, theta, grouping: Grouping, *,
                       trim_multiplier: float | None = 3.0,
                       max_iters: int = 64, tol: float = 1e-8,
                       eps: float = 1e-12):
    """The fused full-round formulation for non-TPU backends: same algorithm
    as ``linreg_round_kernel`` (analytic per-worker gradients -> membership
    matmul means -> resident trim + Weiszfeld), written as flat jnp so XLA
    lowers it well on CPU/GPU.  Agrees with the kernel to float tolerance
    (reduction orders differ along d); the benchmark's "fused" entrant on
    this container's backend."""
    m, n, d = features.shape
    k = grouping.num_batches
    x = features.astype(jnp.float32)
    s = jnp.asarray(assignment_matrix(grouping))
    bsz = jnp.asarray(grouping.batch_sizes, jnp.float32).reshape(k, 1)
    theta = theta.astype(jnp.float32)
    r = jax.lax.dot_general(
        x, theta.reshape(d, 1),
        dimension_numbers=(((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[..., 0] \
        - targets.astype(jnp.float32)                       # (m, n)
    g = jax.lax.dot_general(
        r, x, dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * (1.0 / n)     # (m, d)
    z = jnp.dot(s, g, preferred_element_type=jnp.float32) / bsz
    y = _finish_jnp(_chunked(z, TILE_D), trim_multiplier=trim_multiplier,
                    max_iters=max_iters, tol=tol, eps=eps)
    return y[:d]
