"""Shard-local robust aggregation: the ZeRO-1 contract for every rule.

The paper's server cost is O(md + kd log³N) with d the model dimension —
fine for the linreg testbed and `minitron-4b`, fatal for `qwen2-72b` /
`kimi-k2-1t` where a single gathered (m, d) gradient block exceeds a chip.
The fix is the ZeRO-1 idiom: keep the stacked gradients partitioned over
parameter shards end-to-end and make every registered aggregation rule
operate on per-shard slices:

* **coordinate-wise rules** (``mean``, ``coordinate_median``,
  ``trimmed_mean``, ``coord_median``, ``coord_trimmed_mean``,
  ``random_select``) touch each coordinate independently — they are
  shard-local for free, with NO cross-shard collectives at all;
* **norm-based rules** (``gmom``, ``geomed``, ``gmom_per_leaf``,
  ``norm_select``, ``norm_clip_mean``, ``norm_filter_gmom``, ``krum``)
  need only *scalar-sized* cross-shard reductions: per-shard partial
  squared norms combined into the (k,) norm vectors, one (k, k) partial
  Gram matrix per geometric median for GMoM (its Weiszfeld loop runs on
  it with no collective), and one (m, m) partial distance reduction for
  krum.

:class:`ShardSpec` describes how the stacked gradients are partitioned and
which execution mode combines the partials:

* ``"gspmd"``   — dispatch metadata only.  Reductions stay plain ``jnp``
  and GSPMD inserts the cross-shard psums; used by the production
  group-mode train step (``launch.steps``), where it additionally pins the
  target backend for ``round_backend`` dispatch and forbids the fused
  round kernel (whose leaf concatenation would force a gather).
* ``"shard_map"`` — the hand-scheduled mode for code running INSIDE
  ``shard_map`` with each device holding its slice: per-shard partials are
  combined by an ``all_gather`` over ``axis`` (stacked in device order)
  followed by an ordered ``sum`` over the shard axis.
* ``"virtual"`` — the single-device oracle of ``"shard_map"``: leaves are
  *gathered* but every reduction is computed in the same canonical blocked
  order — per-shard slice partials, stacked shard-major, then the same
  ordered sum.  Because each slice partial runs the identical ops on the
  identical values as the corresponding device in ``"shard_map"`` mode,
  the two modes are **bit-identical** — this is what makes "sharded and
  gathered aggregation agree exactly" a testable contract
  (tests/test_shardmap_aggregate.py) rather than a tolerance judgement.

Partitioning convention (both blocked modes): a stacked leaf with at least
one parameter dim (``ndim > lead_axes``) is split on its LAST dim, which
must divide evenly by ``num_shards``; a leaf with no parameter dims (e.g.
a stacked scalar parameter, shape ``(m,)``) is replicated and its partial
contribution is *owned by shard 0* — every other shard adds an exact zero,
so the ordered sum is unchanged bit for bit.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# repro: bit-stable — reductions in this module must keep a fixed expression
# tree across fusion contexts: use the unrolled chain_sum idiom, never
# jnp.sum/jnp.mean over the shard/member axis (repro.verify RV101/RV105).

_MODES = ("gspmd", "shard_map", "virtual")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How the stacked gradient pytree is partitioned over param shards.

    * ``num_shards``      — shard count along the partitioned (last) dim;
                            1 means "not partitioned" (trivial spec).
    * ``mode``            — ``"gspmd"`` / ``"shard_map"`` / ``"virtual"``
                            (see module docstring).
    * ``axis``            — mesh axis name carrying the shards
                            (``shard_map`` mode's all_gather axis).
    * ``target_backend``  — the backend the lowered program will RUN on
                            (``"tpu"``/``"cpu"``/...); threads through
                            ``aggregators.resolve_round_backend`` so a
                            dry-run sweep lowering TPU mesh programs from a
                            CPU host dispatches the production path, not
                            the host's.  None = use the live backend.
    """
    num_shards: int = 1
    mode: str = "gspmd"
    axis: str = "model"
    target_backend: str | None = None

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(f"unknown ShardSpec mode {self.mode!r}; "
                             f"have {_MODES}")
        if self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got "
                             f"{self.num_shards}")

    @property
    def partitioned(self) -> bool:
        """Stacked gradients arrive as per-shard slices (any mode)."""
        return self.num_shards > 1

    @property
    def blocked(self) -> bool:
        """Reductions must use the canonical blocked order (the
        hand-scheduled ``shard_map`` mode or its ``virtual`` oracle)."""
        return self.partitioned and self.mode in ("shard_map", "virtual")


def target_backend_of(spec: ShardSpec | None) -> str | None:
    return spec.target_backend if spec is not None else None


def is_partitioned(spec: ShardSpec | None) -> bool:
    return spec is not None and spec.partitioned


def shard_slice(leaf, index: int, num_shards: int):
    """Slice ``index`` of ``num_shards`` even splits of the LAST dim."""
    d = leaf.shape[-1]
    if d % num_shards != 0:
        raise ValueError(
            f"last dim {d} of leaf {leaf.shape} does not divide into "
            f"{num_shards} shards")
    c = d // num_shards
    return jax.lax.slice_in_dim(leaf, index * c, (index + 1) * c,
                                axis=leaf.ndim - 1)


def blocked_partial_sum(spec: ShardSpec | None, items, partial_fn, *,
                        shape=(), lead_axes: int = 1):
    """Canonical f32 sum of per-item coordinate reductions, blocked by shard.

    ``items`` is a sequence of leaves (or tuples of leaves sharing their
    trailing coordinate dims); ``partial_fn(*item) -> f32 array of
    ``shape``'' reduces one item's (slice of) coordinates — e.g. per-batch
    squared distances (k,), a squared-movement scalar, or krum's (m, m)
    partial gram.  The first ``lead_axes`` axes of each item's FIRST array
    are non-coordinate axes (the stacked k/m axis); an item whose first
    array has no coordinate dims beyond those is replicated and owned by
    shard 0 (see module docstring).

    With a trivial/gspmd spec this is the plain accumulation loop the
    legacy (unsharded) path always ran — bitwise unchanged, so golden
    traces recorded on that path are unaffected.  With a blocked spec the
    result is the ordered shard-major sum of per-shard partials, identical
    bits whether the shards are real devices (``shard_map``) or virtual
    slices of a gathered leaf (``virtual``).
    """
    items = [it if isinstance(it, tuple) else (it,) for it in items]
    blocked = spec is not None and spec.blocked

    if not blocked:
        acc = jnp.zeros(shape, jnp.float32)
        for it in items:
            acc = acc + partial_fn(*it)
        return acc

    s = spec.num_shards

    def sharded(first, *, check_divisible: bool) -> bool:
        """A leaf with coordinate dims beyond the lead axes is partitioned.

        Divisibility of the last dim is only checkable in ``virtual`` mode,
        where the full leaf is visible; in ``shard_map`` mode the arrays
        are already the local slices (the mesh sharding performed — and
        validated — the split)."""
        if first.ndim <= lead_axes:
            return False
        if check_divisible and first.shape[-1] % s != 0:
            raise ValueError(
                f"leaf {first.shape} has coordinate dims but its last dim "
                f"does not divide into num_shards={s}; shard-local "
                "aggregation requires an even last-dim split")
        return True

    def chain_sum(parts_sk):
        # Ordered shard-major combine as an UNROLLED add chain.  A single
        # ``jnp.sum(axis=0)`` over the shard axis is NOT bit-stable here:
        # XLA may reassociate the s-element reduction differently depending
        # on what it fuses with downstream (observed: 1-ulp drift between
        # the virtual and shard_map lowerings of the same Weiszfeld step).
        # An explicit left-to-right add chain has a fixed expression tree in
        # both modes; s is a device count, so unrolling is cheap.
        acc = parts_sk[0]
        for i in range(1, s):
            acc = acc + parts_sk[i]
        return acc

    if spec.mode == "virtual":
        parts = []
        for i in range(s):
            acc = jnp.zeros(shape, jnp.float32)
            for it in items:
                if sharded(it[0], check_divisible=True):
                    acc = acc + partial_fn(
                        *[shard_slice(a, i, s) for a in it])
                elif i == 0:
                    acc = acc + partial_fn(*it)
            parts.append(acc)
        return chain_sum(jnp.stack(parts))

    # shard_map mode: every array in a sharded item is already the local
    # slice; replicated items contribute on shard 0 only (exact zeros
    # elsewhere keep the ordered sum bit-identical to the virtual oracle).
    on_shard0 = jax.lax.axis_index(spec.axis) == 0
    acc = jnp.zeros(shape, jnp.float32)
    for it in items:
        if sharded(it[0], check_divisible=False):
            acc = acc + partial_fn(*it)
        else:
            p = partial_fn(*it)
            acc = acc + jnp.where(on_shard0, p, jnp.zeros_like(p))
    parts = jax.lax.all_gather(acc, spec.axis, axis=0)   # (s,) + shape
    return chain_sum(parts)
